"""Expert kernel: its least time in the decode program, the touched held
experts' bf16 weights (gate, up and down: 3 x hidden x moe width x 2 B
each) and the routed rows' activations (bf16 in, f32 out) moved once at
the chip's HBM rate, per decode step from the engine's counters
``moe_experts_touched`` and ``moe_choices_held`` over ``decode_steps``;
over the device time of ``moe_expert_ffn`` in the decode program, from
the trace.  Nothing where the engine keeps no such counter."""

PROGRAM = "decode_fn"
KERNEL = "moe_expert_ffn"
BF16, F32 = 2, 4


def floor_bytes(dims: dict, touched: float, rows: float) -> float:
    """Least bytes of one step's expert kernel calls: ``touched`` experts'
    weights and ``rows`` routed rows, summed over the MoE layers."""
    d, ff = dims["hidden_size"], dims["moe_intermediate_size"]
    return touched * 3 * d * ff * BF16 + rows * d * (BF16 + F32)


def read(win):
    if not win.trace or "moe_experts_touched" not in win.stats_open:
        return None
    steps = win.delta("decode_steps")
    execs = sum(v["count"] for k, v in win.trace["programs"].items()
                if PROGRAM in k)
    ops = [v for p, d in win.trace["per_program"].items() if PROGRAM in p
           for k, v in d.items() if KERNEL in k]
    spent = sum(v["seconds"] for v in ops)
    if not steps or not execs or not spent:
        return None
    per_step = floor_bytes(win.dims, win.delta("moe_experts_touched") / steps,
                           win.delta("moe_choices_held") / steps)
    return 100.0 * execs * per_step / win.peaks["hbm_bytes_per_s"] / spent
