"""Names of the host spans the trainer records in the profiler's trace.

``Trainer.fit`` opens them as ``jax.profiler`` annotations, so they land
in the same ``.xplane.pb`` as the device's ops, on the clock those use.
With the profiler off an annotation costs about a microsecond.

=========================  ===================================================
``repro.train.step``       one step's loop body (``StepTraceAnnotation``,
                           ``step_num`` = the step); parent of the rest
``repro.train.batch``      ``make_batch(i)``: the host makes the batch
``repro.train.put``        ``_put_batch``: the batch's transfer is issued
``repro.train.dispatch``   the call of the jitted step, once per attempt
``repro.train.wait``       ``block_until_ready`` on the step's loss
``repro.train.readback``   the metrics read to the host, the step's report
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
