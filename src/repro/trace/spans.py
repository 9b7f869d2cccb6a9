"""Names of the host spans the trainer records in the profiler's trace.

``Trainer.fit`` opens them as ``jax.profiler`` annotations, so they land
in the same ``.xplane.pb`` as the device's ops, on the clock those use.
With the profiler off an annotation costs about a microsecond.
``fit`` keeps one step in flight, so the ``wait`` and ``readback`` inside
step i's span belong to step i-1; a step resolved outside ``fit`` (a read
of ``Trainer.report``) records no span.

=========================  ===================================================
``repro.train.step``       one step's loop body (``StepTraceAnnotation``,
                           ``step_num`` = the step); parent of the rest
``repro.train.batch``      ``make_batch(i)``: the host makes the batch
``repro.train.put``        ``_put_batch``: the batch's transfer is issued
``repro.train.dispatch``   the call of the jitted step, once per attempt
``repro.train.wait``       ``block_until_ready`` on the previous step's
                           loss (the step in flight when this one was
                           dispatched); wraps nothing when none was
``repro.train.readback``   the previous step's metrics read to the host,
                           its report; wraps nothing when none was
``repro.train.checkpoint`` an asynchronous checkpoint's save, or its wait
=========================  ===================================================
"""

STEP = "repro.train.step"
BATCH = "repro.train.batch"
PUT = "repro.train.put"
DISPATCH = "repro.train.dispatch"
WAIT = "repro.train.wait"
READBACK = "repro.train.readback"
CHECKPOINT = "repro.train.checkpoint"
