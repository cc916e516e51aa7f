import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

# NOTE: the two lines above MUST precede every other import (jax locks the
# device count at first init), which is why this module has no
# `from __future__ import annotations`.

DOC = """Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell and extract the roofline terms.

MUST be run as its own process (the two lines above execute before any
other import so jax initializes with 512 host devices):

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single

Per cell this script:
  1. builds parameter/optimizer/batch ShapeDtypeStructs (no allocation),
  2. ``jax.jit(step, in_shardings=…, out_shardings=…).lower(...).compile()``
     against the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  3. records ``compiled.memory_analysis()`` (fits-in-HBM proof),
     ``cost_analysis()`` (FLOPs / bytes) and the collective payload parsed
     from the post-SPMD HLO text,
  4. derives the three roofline terms (seconds):
        compute    = FLOPs / (chips × 197e12)
        memory     = bytes / (chips × 819e9)
        collective = collective_bytes / (chips × 50e9)
  5. appends the row to ``results/dryrun.json`` (incremental — safe to
     re-run; finished cells are skipped unless --force).

``train_*`` cells lower the full ``train_step`` (fwd+bwd+AdamW update);
``prefill_*`` cells lower ``prefill``; ``decode_*``/``long_*`` cells lower
``serve_step`` (one token against a seq_len KV cache), per the assignment.
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (
    logical_to_spec,
    mesh_context,
    param_shardings,
)
from repro.launch.mesh import HW, make_production_mesh
from repro.models.config import SHAPES
from repro.models.registry import ARCH_IDS, get_arch
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainStepConfig, make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun.json"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _bytes_of_shape(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    size = _DTYPE_BYTES.get(dt)
    if size is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * size


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the post-SPMD HLO.

    Per-device convention: shapes in partitioned HLO are per-device buffers;
    the reported number is the per-device collective payload proxy (ring
    traffic ≈ payload × (n-1)/n for AG/RS).
    """
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        for coll in _COLLECTIVES:
            # "  name = bf16[..] all-gather(...)" / fusion-wrapped "%x = ... all-gather-start"
            if f" {coll}(" in s or f" {coll}-start(" in s:
                eq = s.split(" = ", 1)
                if len(eq) != 2:
                    continue
                rhs = eq[1]
                # output shape token(s): up to the op name; tuples "(a, b)"
                head = rhs.split(coll)[0].strip()
                head = head.strip("(")
                toks = re.findall(r"\w+\[[\d,]*\]", head)
                b = sum(_bytes_of_shape(t) for t in toks)
                out[coll]["count"] += 1
                out[coll]["bytes"] += b
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items() if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# sharding construction per cell
# ---------------------------------------------------------------------------


def _dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _batch_sharding(mesh, shape, batch_axis=0):
    """Shard the batch dim over dp when divisible, else replicate."""
    axes = [None] * len(shape)
    if shape[batch_axis] % _dp_size(mesh) == 0:
        axes[batch_axis] = "dp"
    return NamedSharding(mesh, logical_to_spec(mesh, axes))


def _input_shardings(mesh, specs, opts=frozenset()):
    def one(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        ps = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if ps.startswith("cache"):
            # cache leaves: (L, B, ...) — batch at axis 1
            if "kv_seq_shard" in opts:
                # flash-decoding layout: KV sequence (dim 2 of k/v, rank 5)
                # sharded over `model`; GSPMD turns the softmax over the
                # sharded axis into tiny all-reduces (max + sum + out).
                axes = [None] * len(shape)
                if shape[1] % _dp_size(mesh) == 0:
                    axes[1] = "dp"
                key = ps.split("/")[-1]
                nm = mesh.shape.get("model", 1)
                if key in ("k", "v", "cross_k", "cross_v") and len(shape) == 5 \
                        and shape[2] % nm == 0:
                    axes[2] = "model"
                elif key == "ssm" and len(shape) == 5 and shape[2] % nm == 0:
                    axes[2] = "model"  # SSM heads
                elif key == "conv" and len(shape) == 4 and shape[3] % nm == 0:
                    axes[3] = "model"
                return NamedSharding(mesh, logical_to_spec(mesh, axes))
            return _batch_sharding(mesh, shape, batch_axis=1)
        if ps.startswith("positions"):
            return _batch_sharding(mesh, shape, batch_axis=1)  # (3, B, S)
        return _batch_sharding(mesh, shape, batch_axis=0)

    return jax.tree_util.tree_map_with_path(one, specs)


def _opt_shardings(mesh, p_sh):
    mu = jax.tree_util.tree_map(lambda s: {"m": s, "v": s}, p_sh)
    return {"step": NamedSharding(mesh, P()), "mu": mu}


def _opt_shardings_int8(mesh, state_sds, p_sh):
    """int8 moments quantized along the param's last axis keep the param's
    leading structure: q (…lead, nb, 64) and scale (…lead, nb) inherit the
    parameter's PartitionSpec with the last-axis assignment moved onto nb.
    (The earlier flat ZeRO layout forced TB-scale reshards — §Perf C1.)"""
    from repro.distributed.sharding import divisible_spec

    def per_param(sharding, mu_sds):
        spec = list(sharding.spec)

        def shard_like(leaf, extra_none):
            axes = list(spec)
            while len(axes) < len(leaf.shape) - (1 if extra_none else 0):
                axes.append(None)
            axes = axes[: len(leaf.shape) - (1 if extra_none else 0)]
            if extra_none:
                axes.append(None)
            return NamedSharding(mesh, divisible_spec(mesh, axes, leaf.shape))

        out = {}
        for mv in ("m", "v"):
            qt = mu_sds[mv]  # QuantizedTensor SDS pytree: leaves q, scale
            out[mv] = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(qt),
                [shard_like(l, extra_none=(l.ndim == len(spec) + 1))
                 for l in jax.tree_util.tree_leaves(qt)],
            )
        return out

    return jax.tree_util.tree_map(
        per_param, p_sh, state_sds,
        is_leaf=lambda x: isinstance(x, NamedSharding),
    )


# ---------------------------------------------------------------------------
# the per-cell dry run
# ---------------------------------------------------------------------------


OPTS = (
    "kv_seq_shard",     # decode KV/SSM cache sharded over `model` (flash-
                        # decoding split-KV via GSPMD) — memory + collective
    "donate_cache",     # serve_step donates the cache (in-place update)
    "chunked_prefill",  # flash-style chunked attention scores (memory)
    "microbatch8",      # 8-way gradient accumulation (train activations)
    "int8_moments",     # 8-bit blockwise Adam moments, ZeRO-sharded
)


# The CPU backend emulates bf16 by converting to f32 around every op; the
# converts and f32 working copies are artifacts that do not exist on TPU
# and they dominated early byte attributions (EXPERIMENTS.md §Perf, A5).
# The dry-run therefore lowers everything in UNIFORM f32 and scales byte
# and collective terms by 0.5 to model native-bf16 execution.  (fp32-by-
# design tensors — router logits, softmax stats — are small; the 0.5 is
# applied uniformly and noted as an approximation.)
BYTE_SCALE = 0.5


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             check_fit: bool = True, opts: frozenset = frozenset()) -> dict:
    import dataclasses as _dc

    arch = get_arch(arch_name)
    cfg_new = _dc.replace(arch.cfg, param_dtype="float32",
                          compute_dtype="float32")
    if "chunked_prefill" in opts:
        cfg_new = _dc.replace(cfg_new, attn_chunk=512)
    arch = _dc.replace(arch, cfg=cfg_new)
    cfg = arch.cfg
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return {"skipped": "full attention cannot serve 524k context (see DESIGN.md)"}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    specs = arch.input_specs(shape)

    params_sds = jax.eval_shape(lambda: arch.init(jax.random.PRNGKey(0)))
    p_sh = param_shardings(mesh, params_sds)
    in_sh = _input_shardings(mesh, specs, opts)

    t0 = time.time()
    with mesh_context(mesh):
        if shape.kind == "train":
            opt_cfg = AdamWConfig(
                moments_dtype="int8" if "int8_moments" in opts else "float32"
            )
            init_state, train_step = make_train_step(
                arch, opt_cfg,
                TrainStepConfig(
                    donate=False,
                    microbatches=8 if "microbatch8" in opts else 1,
                ),
                mesh=mesh,
            )
            state_sds = jax.eval_shape(init_state, params_sds)
            if "int8_moments" in opts:
                s_sh = {"opt": {"step": NamedSharding(mesh, P()),
                                "mu": _opt_shardings_int8(
                                    mesh, state_sds["opt"]["mu"], p_sh)}}
            else:
                s_sh = {"opt": _opt_shardings(mesh, p_sh)}
            step_fn = jax.jit(
                train_step,
                in_shardings=(p_sh, s_sh, in_sh),
                out_shardings=(p_sh, s_sh, None),
            )
            lowered = step_fn.lower(params_sds, state_sds, specs)
        elif shape.kind == "prefill":
            def prefill_step(params, batch):
                return arch.prefill(params, batch)

            step_fn = jax.jit(prefill_step, in_shardings=(p_sh, in_sh))
            lowered = step_fn.lower(params_sds, specs)
        else:  # decode → serve_step
            def serve_step(params, token, cache, lengths):
                return arch.decode_step(params, token, cache, lengths)

            step_fn = jax.jit(
                serve_step,
                in_shardings=(p_sh, in_sh["token"], in_sh["cache"], in_sh["lengths"]),
                out_shardings=(None, in_sh["cache"]),
                donate_argnums=(2,) if "donate_cache" in opts else (),
            )
            lowered = step_fn.lower(
                params_sds, specs["token"], specs["cache"], specs["lengths"]
            )
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)  # static (per-program-text) counts

    # Loop-aware per-device cost: XLA's cost_analysis reports while bodies
    # once; analyze_hlo multiplies by trip counts (see hlo_cost.py).
    from repro.launch.hlo_cost import analyze_hlo

    cost = compiled.cost_analysis()

    lcost = analyze_hlo(hlo)
    flops = lcost.flops
    bytes_accessed = lcost.bytes * BYTE_SCALE
    t_compute = flops / HW["peak_bf16_flops"]
    t_memory = bytes_accessed / HW["hbm_bw"]
    t_coll = lcost.collective_bytes * BYTE_SCALE / HW["ici_bw"]
    dominant = max(
        [("compute", t_compute), ("memory", t_memory), ("collective", t_coll)],
        key=lambda kv: kv[1],
    )[0]

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * shape.global_batch  # one token/request
    model_flops_per_chip = model_flops / n_chips

    # memory_analysis object fields vary; fall back to str parsing
    mem_str = str(mem)

    row = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_kind,
        "opts": sorted(opts),
        "chips": n_chips,
        "step": shape.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collectives": coll,
        "collective_bytes_loop_aware": lcost.collective_bytes,
        "collective_counts_loop_aware": lcost.collective_counts,
        "raw_cost_analysis_flops": float(cost.get("flops", 0.0)),
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": (model_flops_per_chip / flops) if flops else None,
        "params": n_params,
        "active_params": n_active,
        "memory_analysis": mem_str[:2000],
        "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
    }
    if check_fit and row["temp_size_bytes"] is not None:
        # arguments are sharded live buffers; temp is transient; the f32
        # lowering doubles what bf16 would occupy → scale back
        live = ((row["argument_size_bytes"] or 0)
                + (row["temp_size_bytes"] or 0)) * BYTE_SCALE
        row["hbm_fit"] = bool(live <= HW["hbm_bytes"])
        row["live_bytes"] = live
    return row


def _load_results() -> dict:
    if RESULTS.exists():
        return json.loads(RESULTS.read_text())
    return {}


def _save_results(res: dict) -> None:
    RESULTS.parent.mkdir(parents=True, exist_ok=True)
    tmp = RESULTS.with_suffix(".tmp")
    tmp.write_text(json.dumps(res, indent=1, default=str))
    os.replace(tmp, RESULTS)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--opt", default="",
                    help=f"comma-joined optimizations from {OPTS}")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]
    opts = frozenset(o for o in args.opt.split(",") if o)
    for o in opts:
        assert o in OPTS, f"unknown opt {o!r}"
    suffix = ("|" + "+".join(sorted(opts))) if opts else ""

    results = _load_results()
    for a in archs:
        for s in shapes:
            for m in meshes:
                key = f"{a}|{s}|{m}{suffix}"
                if key in results and not args.force and "error" not in results[key]:
                    print(f"[skip] {key}")
                    continue
                print(f"[cell] {key} ...", flush=True)
                try:
                    row = run_cell(a, s, m, opts=opts)
                except Exception as e:  # noqa: BLE001
                    row = {"error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"  ERROR {e}")
                results[key] = row
                _save_results(results)
                if "error" not in row and "skipped" not in row:
                    print(
                        f"  ok lower={row['lower_s']}s compile={row['compile_s']}s "
                        f"dominant={row['dominant']} "
                        f"t=({row['t_compute_s']:.3e},{row['t_memory_s']:.3e},"
                        f"{row['t_collective_s']:.3e})s"
                    )
    print("done:", RESULTS)


if __name__ == "__main__":
    main()
