"""The yardstick: the least work each layer has to do, from shapes alone.

Whatever implements a layer, its floor is counted here from the model's
sizes and the step's shapes, so a later kernel swap does not move it.
``dims`` holds the configuration file's keys, by their published names:

  hidden_size, num_hidden_layers, num_attention_heads, vocab_size
  num_key_value_heads, head_dim   grouped-query attention (``head_dim``
                                  defaults to hidden_size / heads)
  intermediate_size               the dense FFN's width
  kv_lora_rank, qk_rope_head_dim, latent attention (MLA): one cached vector
  qk_nope_head_dim, v_head_dim    of kv_lora_rank + qk_rope_head_dim per
                                  token and layer, no q compression
  n_routed_experts,               sparse experts: every layer from
  num_experts_per_tok,            ``first_k_dense_replace`` on whose index
  moe_intermediate_size,          ``moe_layer_freq`` divides is a MoE layer,
  n_shared_experts,               in which a token multiplies its
  first_k_dense_replace,          num_experts_per_tok + n_shared_experts
  moe_layer_freq                  experts and the router

Every key is read through ``value``, which ``harness.build_cfg`` holds to
the system's config as well, so the yardstick counts the model that runs.
``peaks`` is this device's row of ``bench/peaks.json``.
"""

from __future__ import annotations

BF16 = 2

# what the yardstick takes for a key that the file leaves out: no experts,
# none shared, no leading dense layers, every layer MoE, no latent cache
DEFAULTS = {"n_routed_experts": 0, "n_shared_experts": 0,
            "first_k_dense_replace": 0, "moe_layer_freq": 1,
            "kv_lora_rank": 0}


def value(dims: dict, key: str):
    """``key``'s number as the yardstick counts it: the file's, else its
    default; ``head_dim`` of a cache that is not latent defaults to
    hidden_size / heads.  KeyError for a key it has no number for."""
    if key in dims:
        return dims[key]
    if key == "head_dim" and not latent(dims):
        return dims["hidden_size"] // dims["num_attention_heads"]
    return DEFAULTS[key]


def head_dim(dims: dict) -> int:
    return value(dims, "head_dim")


def latent(dims: dict) -> bool:
    return bool(value(dims, "kv_lora_rank"))


def qk_head_dim(dims: dict) -> int:
    if latent(dims):
        return dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
    return head_dim(dims)


def v_head_dim(dims: dict) -> int:
    return dims["v_head_dim"] if latent(dims) else head_dim(dims)


def attn_projections(dims: dict) -> list[tuple[int, int]]:
    """(K, N) of each attention projection of one layer."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    if latent(dims):
        r, rope = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
        return [(d, h * qk_head_dim(dims)), (d, r + rope),
                (r, h * (dims["qk_nope_head_dim"] + v_head_dim(dims))),
                (h * v_head_dim(dims), d)]
    q = h * head_dim(dims)
    kv = dims["num_key_value_heads"] * head_dim(dims)
    return [(d, q), (d, kv), (d, kv), (q, d)]


def ffn_projections(d: int, ff: int) -> list[tuple[int, int]]:
    """(K, N) of a SwiGLU FFN's gate, up and down projections."""
    return [(d, ff), (d, ff), (ff, d)]


def moe_layers(dims: dict) -> tuple[int, ...]:
    """Indices of the layers that route their tokens over experts."""
    if not value(dims, "n_routed_experts"):
        return ()
    freq = value(dims, "moe_layer_freq")
    return tuple(i for i in range(value(dims, "first_k_dense_replace"),
                                  dims["num_hidden_layers"]) if i % freq == 0)


def layer_counts(dims: dict) -> tuple[int, int]:
    """(dense layers, MoE layers)."""
    moe = len(moe_layers(dims))
    return dims["num_hidden_layers"] - moe, moe


def projections(dims: dict, moe: bool = False) -> list[tuple[int, int]]:
    """(K, N) of each projection one token multiplies in one dense layer,
    or with ``moe`` in one MoE layer: its experts per token and shared
    experts, and the router."""
    d = dims["hidden_size"]
    if not moe:
        return attn_projections(dims) + ffn_projections(
            d, dims["intermediate_size"])
    experts = (dims["num_experts_per_tok"]
               + value(dims, "n_shared_experts"))
    return (attn_projections(dims)
            + experts * ffn_projections(d, dims["moe_intermediate_size"])
            + [(d, dims["n_routed_experts"])])


def matmul_params(dims: dict) -> int:
    """Weights every token multiplies: the projections of every layer (in
    a MoE layer, only the experts it is routed to and the shared ones, and
    the router) and the LM head."""
    n_dense, n_moe = layer_counts(dims)
    total = 0
    if n_dense:
        total += n_dense * sum(k * n for k, n in projections(dims))
    if n_moe:
        total += n_moe * sum(k * n for k, n in projections(dims, moe=True))
    return total + dims["hidden_size"] * dims["vocab_size"]


def model_ops(dims: dict, tokens: int, ctx_sum: int) -> float:
    """Operations of ``tokens`` forward tokens whose attention spans add up
    to ``ctx_sum`` keys: 2 per matmul weight, and per key 2 per head and
    query-key dimension (scores) and 2 per head and value dimension (the
    weighted sum) in each layer."""
    att = dims["num_hidden_layers"] * (
        2 * dims["num_attention_heads"] * (qk_head_dim(dims)
                                           + v_head_dim(dims)))
    return 2.0 * matmul_params(dims) * tokens + att * ctx_sum


def xnor_linear_floor_s(m: int, k: int, n: int, peaks: dict) -> float:
    """Least time of one binarized linear on m rows: one bit per weight,
    beta in f32, x in bf16 and y in f32 moved once, against 2*m*k*n
    operations at the int8 peak (popcount work is integer work)."""
    nbytes = k * n / 8 + 4 * n + BF16 * m * k + 4 * m * n
    return max(nbytes / peaks["hbm_bytes_per_s"],
               2.0 * m * k * n / peaks["int8_ops_per_s"])


def xnor_step_floor_s(dims: dict, rows: int, peaks: dict) -> float:
    """Least time of every binarized linear of one step over ``rows`` rows:
    the attention projections of every layer and the FFN of dense layers
    (the system's ``+xnor`` axis leaves routers and experts in float)."""
    def floor(projs):
        return sum(xnor_linear_floor_s(rows, k, n, peaks) for k, n in projs)
    n_dense, n_moe = layer_counts(dims)
    total = 0.0
    if n_dense:
        total += n_dense * floor(projections(dims))
    if n_moe:
        total += n_moe * floor(attn_projections(dims))
    return total


def kv_bytes_per_token(dims: dict) -> int:
    """Bytes one cached token holds in one layer at the pool's bf16: the
    latent vector and its rotary key, or K and V of every KV head."""
    if latent(dims):
        return BF16 * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"])
    return 2 * BF16 * dims["num_key_value_heads"] * head_dim(dims)


def paged_attn_floor_s(dims: dict, ctx_sum: float, rows: int,
                       peaks: dict) -> float:
    """Least time of one decode step's attention: every live cached token
    read once at the pool's bf16, and q and the output moved once, in
    every layer."""
    q_out = BF16 * dims["num_attention_heads"] * (qk_head_dim(dims)
                                                  + v_head_dim(dims))
    per_layer = kv_bytes_per_token(dims) * ctx_sum + q_out * rows
    return dims["num_hidden_layers"] * per_layer / peaks["hbm_bytes_per_s"]
