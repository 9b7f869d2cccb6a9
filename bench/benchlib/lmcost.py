"""The work a decoder-only transformer's serving calls need, counted from
the configuration's shapes and the runner's record of each call, whatever
implements it.

A call is a prefill of ``valid`` prompt tokens starting at position
``start`` of one sequence, or a decode of one token for each active
sequence, given by its live length (the tokens already in its cache).
What is counted:

- FLOPs: 2 per weight of the layers' matrices per token (the MLP has
  three matrices where its activation is gated, ``swiglu`` or ``geglu``,
  and two otherwise); 4 x heads x
  head size per layer per (query, visible key) pair under the causal mask
  (scores and the weighted sum of values); 2 x d_model x vocabulary per
  row of logits (a prefill gives one, for its last position; a decode one
  per sequence).  Norms, rotary and the softmax's elementwise work are
  left out.
- Bytes of HBM: every weight once per call; the cached K and V of each
  sequence's earlier tokens, read once; the K and V of each new token,
  written once; the embedding rows of the call's tokens; the logits out.

Padded chunk tails and inactive slots count nothing.
"""

from __future__ import annotations


def shapes(cfg: dict) -> dict:
    """What the counts need from a configuration file."""
    keys = ("n_layers", "d_model", "d_ff", "n_heads", "n_kv_heads",
            "head_dim", "vocab_size", "weight_bytes", "kv_bytes",
            "logit_bytes")
    out = {k: int(cfg[k]) for k in keys}
    out["params"] = int(cfg["parameters"])
    out["mlp_matrices"] = 3 if cfg["act"] in GATED else 2
    return out


#: activations whose MLP multiplies a gate by an up projection
GATED = ("swiglu", "geglu")


def _layer_matrix_weights(s: dict) -> int:
    D, H, K, hd = s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"]
    return (2 * D * H * hd + 2 * D * K * hd
            + s["mlp_matrices"] * D * s["d_ff"])


def _kv_bytes_per_token(s: dict) -> int:
    return s["n_layers"] * 2 * s["n_kv_heads"] * s["head_dim"] * s["kv_bytes"]


def _attention_flops(s: dict, pairs: int) -> int:
    return 4 * s["n_heads"] * s["head_dim"] * s["n_layers"] * pairs


def prefill(s: dict, start: int, valid: int) -> tuple[int, int]:
    """(FLOPs, bytes) of a prefill of ``valid`` tokens at ``start``."""
    pairs = valid * start + valid * (valid + 1) // 2
    tokens_flops = 2 * _layer_matrix_weights(s) * s["n_layers"] * valid
    logits_flops = 2 * s["d_model"] * s["vocab_size"]
    flops = tokens_flops + _attention_flops(s, pairs) + logits_flops
    nbytes = (s["params"] * s["weight_bytes"]
              + _kv_bytes_per_token(s) * (start + valid)
              + valid * s["d_model"] * s["weight_bytes"]
              + s["vocab_size"] * s["logit_bytes"])
    return flops, nbytes


def decode(s: dict, lengths: list[int]) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step of the sequences whose live
    lengths are ``lengths``."""
    n = len(lengths)
    pairs = sum(ell + 1 for ell in lengths)
    flops = (2 * _layer_matrix_weights(s) * s["n_layers"] * n
             + _attention_flops(s, pairs)
             + 2 * s["d_model"] * s["vocab_size"] * n)
    nbytes = (s["params"] * s["weight_bytes"]
              + _kv_bytes_per_token(s) * (sum(lengths) + n)
              + n * s["d_model"] * s["weight_bytes"]
              + n * s["vocab_size"] * s["logit_bytes"])
    return flops, nbytes


def call(s: dict, rec: dict) -> tuple[int, int]:
    """(FLOPs, bytes) of one recorded call."""
    if rec["kind"] == "decode":
        return decode(s, rec["lengths"])
    return prefill(s, rec["start"], rec["valid"])
