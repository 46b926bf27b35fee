"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell
and extract memory / cost / collective analyses — the proof that the
distribution config is coherent without real hardware.

MUST be the first two lines, before any other import (jax locks the device
count at first init):
"""
import os  # noqa: E402
# Drop any inherited device-count flag first: XLA takes the LAST occurrence,
# so appending the ambient XLA_FLAGS (e.g. the 8-device CI job's) verbatim
# would silently override the 512-device grid this driver needs.
os.environ["XLA_FLAGS"] = " ".join(
    ["--xla_force_host_platform_device_count=512"]
    + [f for f in os.environ.get("XLA_FLAGS", "").split()
       if not f.startswith("--xla_force_host_platform_device_count")])

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import repro.configs as configs                    # noqa: E402
from repro.configs.base import SHAPES, shape_applicable  # noqa: E402
from repro.distributed import sharding             # noqa: E402
from repro.distributed.ctx import activation_rules  # noqa: E402
from repro.launch import mesh as mesh_mod          # noqa: E402
from repro.models import lm                        # noqa: E402
from repro.roofline import analysis                # noqa: E402
from repro.train import train_step as train_mod    # noqa: E402


# ---------------------------------------------------------------------------
# input specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(cfg, shape) -> dict:
    """Abstract inputs for one (arch, shape) cell — no device allocation."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        out = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
               "labels": jax.ShapeDtypeStruct((b, s), i32)}
    elif shape.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
    else:  # decode: one new token against a seq_len-deep cache
        out = {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    if cfg.n_ctx_tokens and shape.kind != "decode":
        out["ctx"] = jax.ShapeDtypeStruct(
            (b, cfg.n_ctx_tokens, cfg.d_model), jnp.float32)
    return out


def default_q_chunk(cfg, shape, unroll: bool = False) -> int:
    if shape.kind in ("train", "prefill") and shape.seq_len > 8192:
        # unrolled roofline runs use few big chunks (exact costs, bounded
        # HLO size); scan runs use small chunks (bounded VMEM claim).
        return shape.seq_len // 4 if unroll else 2048
    return 0


# ---------------------------------------------------------------------------
# cell builders: (fn, args, in_shardings, out_shardings, donate)
# ---------------------------------------------------------------------------

def build_cell(cfg, shape, mesh, *, rules=None, kv_shard="auto",
               q_chunk=None, microbatch=1, grad_compress="none",
               unroll=False, acc_bf16=False, fsdp_pods=False):
    rules = rules or dict(sharding.DEFAULT_RULES)
    if fsdp_pods and "pod" in mesh.axis_names:
        # ZeRO-3 across BOTH pod and data axes: halves the per-chip
        # param/grad/optimizer floor at the cost of inter-pod (DCN-class)
        # weight all-gathers per layer.
        rules["fsdp"] = ("pod", "data")
    if rules.get("fsdp") == "off":
        # serving configuration: no FSDP — params replicated over the data
        # axis (TP-only sharding).  Kills per-layer weight all-gathers; at
        # inference there is no optimizer state so the memory cost is just
        # params/TP per chip.
        rules["fsdp"] = None
    ba = sharding.batch_axes(mesh, shape.global_batch)
    tp_size = mesh.shape[rules["tp"]]
    if kv_shard == "auto":
        # TP over KV heads when they divide the model axis, else
        # sequence-parallel cache (seq_len always divides).
        kv_shard = "heads" if cfg.n_kv_heads % tp_size == 0 else "seq"
    qc = default_q_chunk(cfg, shape, unroll) if q_chunk is None else q_chunk
    ins = input_specs(cfg, shape)
    has_ctx = "ctx" in ins

    if shape.kind == "train":
        state = train_mod.abstract_state(cfg)
        sspec = train_mod.state_pspecs(cfg, rules)
        bspec = sharding.data_specs(mesh, shape.global_batch, has_ctx)
        step = jax.ShapeDtypeStruct((), jnp.int32)

        def fn(state, batch, step):
            return train_mod.train_step(
                cfg, state, batch, step, q_chunk=qc, microbatch=microbatch,
                grad_compress=grad_compress, mesh=mesh, rules=rules,
                unroll=unroll,
                acc_dtype=jnp.bfloat16 if acc_bf16 else jnp.float32)

        metrics_spec = {k: P() for k in
                        ("ce", "aux", "tokens", "loss", "gnorm", "lr")}
        in_sh = (sharding.tree_named(mesh, sspec),
                 sharding.tree_named(mesh, bspec),
                 NamedSharding(mesh, P()))
        out_sh = (sharding.tree_named(mesh, sspec),
                  sharding.tree_named(mesh, metrics_spec))
        args = (state, ins | {}, step)
        tokens = shape.global_batch * shape.seq_len
        mf = lm.model_flops(cfg, "train", tokens)
        return fn, args, in_sh, out_sh, (0,), mf

    params = lm.abstract_params(cfg)
    pspec = lm.param_pspecs(cfg, rules)

    if shape.kind == "prefill":
        def fn(params, batch):
            logits, state = lm.prefill(cfg, params, batch["tokens"],
                                       batch.get("ctx"), s_max=shape.seq_len,
                                       q_chunk=qc, unroll=unroll)
            return logits, state

        bspec = {"tokens": P(ba, None)}
        if has_ctx:
            bspec["ctx"] = P(ba, None, None)
        st_spec = lm.decode_state_pspecs(cfg, ba, kv_shard, tp_size)
        in_sh = (sharding.tree_named(mesh, pspec),
                 sharding.tree_named(mesh, bspec))
        out_sh = (NamedSharding(mesh, P(ba, None, rules["tp"])),
                  sharding.tree_named(mesh, st_spec))
        args = (params, ins)
        tokens = shape.global_batch * shape.seq_len
        mf = lm.model_flops(cfg, "prefill", tokens)
        return fn, args, in_sh, out_sh, (), mf

    # decode: one token against a resident state of depth seq_len
    state = lm.decode_state_spec(cfg, shape.global_batch, shape.seq_len,
                                 abstract=True)
    st_spec = lm.decode_state_pspecs(cfg, ba, kv_shard, tp_size)

    def fn(params, token, state):
        return lm.decode_step(cfg, params, token, state, unroll=unroll)

    in_sh = (sharding.tree_named(mesh, pspec),
             NamedSharding(mesh, P(ba, None)),
             sharding.tree_named(mesh, st_spec))
    out_sh = (NamedSharding(mesh, P(ba, None, rules["tp"])),
              sharding.tree_named(mesh, st_spec))
    args = (params, ins["tokens"], state)
    mf = lm.model_flops(cfg, "decode", shape.global_batch)
    return fn, args, in_sh, out_sh, (2,), mf


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, **overrides) -> dict:
    import dataclasses
    cfg = configs.get(arch)
    kv_dtype = overrides.pop("kv_dtype", None)
    if kv_dtype:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    mlstm_chunk = overrides.pop("mlstm_chunk", None)
    if mlstm_chunk:
        cfg = dataclasses.replace(cfg, mlstm_chunk=mlstm_chunk)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    fn, args, in_sh, out_sh, donate, model_flops = build_cell(
        cfg, shape, mesh, **overrides)

    rules = overrides.get("rules") or dict(sharding.DEFAULT_RULES)
    act_rules = {"batch": sharding.batch_axes(mesh, shape.global_batch),
                 "tp": rules["tp"], "ep": rules["ep"]}
    t0 = time.time()
    with mesh, activation_rules(act_rules):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # MODEL_FLOPS is global; roofline terms are per chip
    roof = analysis.roofline(compiled, model_flops=model_flops / mesh.size)
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": mesh.size,
        "status": "ok",
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "params": lm.param_count(cfg),
        "active_params": lm.active_param_count(cfg),
        "overrides": {k: str(v) for k, v in overrides.items()},
        **roof,
    }


ALL_SHAPES = list(SHAPES)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=ALL_SHAPES)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--kv-shard", default="auto",
                    choices=["auto", "heads", "seq"])
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "onebit_pod"])
    ap.add_argument("--kv-dtype", default=None, choices=[None, "bf16", "i8"])
    ap.add_argument("--acc-bf16", action="store_true",
                    help="bf16 microbatch gradient accumulator")
    ap.add_argument("--fsdp-pods", action="store_true",
                    help="shard params/optimizer over pod axis too (ZeRO-3 "
                         "across pods)")
    ap.add_argument("--fsdp-off", action="store_true",
                    help="serving config: replicate params over data axis "
                         "(TP-only)")
    ap.add_argument("--mlstm-chunk", type=int, default=None)
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer stacks for exact cost/collective "
                         "analysis (roofline runs); scan is the compile-"
                         "time-friendly default for the multi-pod proof")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else sorted(configs.ALL)
    shapes = [args.shape] if args.shape else ALL_SHAPES
    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes[args.mesh]:
                tagsuf = f"_{args.tag}" if args.tag else ""
                out_path = os.path.join(
                    args.out, f"{arch}_{shape_name}_"
                    f"{'multi' if mp else 'single'}{tagsuf}.json")
                try:
                    res = run_cell(arch, shape_name, mp,
                                   kv_shard=args.kv_shard,
                                   q_chunk=args.q_chunk,
                                   microbatch=args.microbatch,
                                   grad_compress=args.grad_compress,
                                   unroll=args.unroll,
                                   kv_dtype=args.kv_dtype,
                                   mlstm_chunk=args.mlstm_chunk,
                                   acc_bf16=args.acc_bf16,
                                   fsdp_pods=args.fsdp_pods,
                                   rules=(dict(sharding.DEFAULT_RULES,
                                               fsdp="off")
                                          if args.fsdp_off else None))
                except Exception as e:  # a failing cell is a bug: report it
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                with open(out_path, "w") as f:
                    json.dump(res, f, indent=2)
                line = (f"{res['status']:8s} {arch} {shape_name} "
                        f"{res['mesh']}")
                if res["status"] == "ok":
                    line += (f"  bottleneck={res['bottleneck']}"
                             f" t=({res['t_compute_s']*1e3:.1f},"
                             f"{res['t_memory_s']*1e3:.1f},"
                             f"{res['t_collective_s']*1e3:.1f})ms"
                             f" compile={res['t_compile_s']:.0f}s")
                elif res["status"] == "error":
                    line += "  " + res["error"][:120]
                print(line, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
