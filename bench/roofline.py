"""The yardstick: the least work each layer has to do, from shapes alone.

Whatever implements a layer, its floor is counted here from the model's
sizes and the step's shapes, so a later kernel swap does not move it.
``dims`` holds the configuration file's keys (``hidden_size``, ...);
``peaks`` is this device's row of ``bench/peaks.json``.
"""

from __future__ import annotations

BF16 = 2


def projections(dims: dict) -> list[tuple[int, int]]:
    """(K, N) of each projection of one decoder layer."""
    d, ff = dims["hidden_size"], dims["intermediate_size"]
    q = dims["num_attention_heads"] * dims["head_dim"]
    kv = dims["num_key_value_heads"] * dims["head_dim"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]


def matmul_params(dims: dict) -> int:
    """Weights every token multiplies: all projections and the LM head."""
    per_layer = sum(k * n for k, n in projections(dims))
    return (dims["num_hidden_layers"] * per_layer
            + dims["hidden_size"] * dims["vocab_size"])


def model_ops(dims: dict, tokens: int, ctx_sum: int) -> float:
    """Operations of ``tokens`` forward tokens whose attention spans add up
    to ``ctx_sum`` keys: 2 per matmul weight, and 4 per key per head and
    head dimension in each layer (scores and the weighted sum)."""
    att = (4 * dims["num_hidden_layers"] * dims["num_attention_heads"]
           * dims["head_dim"])
    return 2.0 * matmul_params(dims) * tokens + att * ctx_sum


def xnor_linear_floor_s(m: int, k: int, n: int, peaks: dict) -> float:
    """Least time of one binarized linear on m rows: one bit per weight,
    beta in f32, x in bf16 and y in f32 moved once, against 2*m*k*n
    operations at the int8 peak (popcount work is integer work)."""
    nbytes = k * n / 8 + 4 * n + BF16 * m * k + 4 * m * n
    return max(nbytes / peaks["hbm_bytes_per_s"],
               2.0 * m * k * n / peaks["int8_ops_per_s"])


def xnor_step_floor_s(dims: dict, rows: int, peaks: dict) -> float:
    """Least time of every binarized linear of one step over ``rows`` rows."""
    return dims["num_hidden_layers"] * sum(
        xnor_linear_floor_s(rows, k, n, peaks) for k, n in projections(dims))


def paged_attn_floor_s(dims: dict, ctx_sum: float, rows: int,
                       peaks: dict) -> float:
    """Least time of one decode step's attention: every live cached key and
    value read once at the pool's bf16, and q and the output moved once,
    in every layer."""
    kv = dims["num_key_value_heads"] * dims["head_dim"]
    q = dims["num_attention_heads"] * dims["head_dim"]
    per_layer = 2 * BF16 * kv * ctx_sum + 2 * BF16 * q * rows
    return dims["num_hidden_layers"] * per_layer / peaks["hbm_bytes_per_s"]
