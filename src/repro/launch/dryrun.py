"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract roofline inputs from the compiled artifact.

MUST be the first import in the process: the placeholder-device flag below
has to be set before jax initializes its backends. Do NOT move it, and do NOT
set it anywhere global (tests/benches must see 1 device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo_1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out report.json]
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import SHAPES, get_arch, runnable_cells  # noqa: E402
from repro.core.admm import SalaadConfig                         # noqa: E402
from repro.core.selection import SelectionConfig, select_blocks  # noqa: E402
from repro.launch.mesh import make_production_mesh               # noqa: E402
from repro.models import model                                   # noqa: E402
from repro.optim.adam import AdamConfig                          # noqa: E402
from repro.parallel.sharding import (                            # noqa: E402
    batch_shardings,
    dp_axes,
    dp_size as _dp_size,
    param_sharding_tree,
)
from repro.train.state import abstract_train_state               # noqa: E402
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step  # noqa: E402

# ------------------------------------------------------------ hardware ----
# TPU v5e per chip (roofline constants; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s/link


# dp_axes / _dp_size / batch_shardings moved to repro.parallel.sharding —
# one axis-naming authority shared with ServingMesh and the serving engines.


def cache_shardings(cache_abstract, mesh):
    """Heuristic cache sharding: heads/model, batch/data (or seq/data at B=1)."""
    dp = dp_axes(mesh)
    dpn = _dp_size(mesh)
    model_n = mesh.shape["model"]

    def one(leaf):
        s = leaf.shape
        if len(s) == 5:  # (stack, B, H, S, D)
            spec = [None] * 5
            if s[2] % model_n == 0:
                spec[2] = "model"
            elif s[4] % model_n == 0:
                # GQA head counts (8/10/12/20) rarely divide the model axis;
                # shard head_dim instead — attention contracts over D, GSPMD
                # emits a psum. Unsharded caches cost up to 214 GB/device
                # (qwen1.5 decode_32k, measured baseline).
                spec[4] = "model"
            if s[1] % dpn == 0:
                spec[1] = dp
            elif s[3] % dpn == 0:
                spec[3] = dp
            return NamedSharding(mesh, P(*spec))
        if len(s) == 4:  # (stack, B, K, C) conv window
            spec = [None] * 4
            if s[1] % dpn == 0:
                spec[1] = dp
            if s[3] % model_n == 0:
                spec[3] = "model"
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, cache_abstract)


def slr_shardings(slr_abstract, params_shardings, mesh):
    """Surrogate tensors follow their weight's sharding (DESIGN.md §3)."""
    from repro.core.admm import BlockSLR

    flat_params = {}

    def record(path, leaf):
        from repro.core.selection import path_str

        flat_params[path_str(path)] = leaf
        return leaf

    jax.tree_util.tree_map_with_path(record, params_shardings)

    out = {}
    for name, blk in slr_abstract.items():
        wspec = flat_params.get(name)
        wp = wspec.spec if wspec is not None else P()
        # weight spec covers (stack..., n, m)
        n_ax = wp[-2] if len(wp) >= 2 else None
        m_ax = wp[-1] if len(wp) >= 1 else None
        stack = tuple(wp[:-2]) if len(wp) > 2 else (None,) * (blk.y.ndim - 2)
        ns = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
        # COO capacity dim: shard over every mesh axis not already used by the
        # stacked dims (experts use 'model'); replicated COO buffers cost
        # ~1 GB/device at dbrx scale (measured).
        used = {a for s in stack if s for a in ((s,) if isinstance(s, str) else s)}
        free_axes = tuple(a for a in mesh.axis_names if a not in used)
        cap = blk.s_coo.values.shape[-1]
        free_n = int(np.prod([mesh.shape[a] for a in free_axes])) if free_axes else 1
        cap_ax = free_axes if free_axes and cap % free_n == 0 else None
        out[name] = type(blk)(
            p=ns(*stack, n_ax, None),
            vt=ns(*stack, None, m_ax),
            s_vals=ns(*stack, None),
            s_coo=type(blk.s_coo)(
                values=ns(*stack, cap_ax), idx=ns(*stack, cap_ax), shape=blk.s_coo.shape
            ),
            y=ns(*stack, n_ax, m_ax),
            z=ns(*stack, n_ax, m_ax),
            alpha=ns(*stack),
            beta=ns(*stack),
            rho=blk.rho,
        )
    return out


COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
SHAPE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = \(?([a-z0-9]+)\[([0-9,]*)\]")

DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand sizes of every collective op in the compiled HLO."""
    totals: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        if f"{kind}(" not in line and f"{kind}-start(" not in line and f"{kind}-done(" not in line:
            continue
        if f"{kind}-done(" in line:
            continue  # avoid double counting start/done pairs
        # parse all shapes on the lhs (may be a tuple)
        lhs = line.split("=", 1)[0] + "= " + line.split("=", 1)[1]
        shapes = re.findall(r"([a-z0-9]+)\[([0-9,]*)\]", line.split("(", 1)[0].split("=", 1)[1])
        nbytes = 0
        for dt, dims in shapes:
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        totals[kind] = totals.get(kind, 0) + nbytes
    totals["total"] = sum(v for k, v in totals.items() if k != "total")
    return totals


_MAJOR_OPS = {
    "dot", "convolution", "scatter", "gather", "reduce", "reduce-window",
    "sort", "concatenate", "dynamic-update-slice", "dynamic-slice",
    "transpose", "copy", "fusion", "select-and-scatter", "rng-bit-generator",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "cumsum", "exponential",
}
_OPCODE_RE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPES_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def fusion_aware_hbm_bytes(hlo_text: str) -> float:
    """Fusion-aware HBM-traffic estimate from the per-device HLO.

    XLA's raw "bytes accessed" treats every elementwise intermediate as HBM
    traffic; on TPU those chains fuse. We count 2x (write + read-back) the
    output bytes of ops that genuinely materialize data (matmuls, reductions,
    scatters/gathers, transposes, collectives) and skip fusable elementwise
    ops. Methodology recorded in EXPERIMENTS.md §Roofline; it is an estimate
    between the resident-bytes lower bound and the unfused upper bound.
    """
    total = 0.0
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        m = _OPCODE_RE.search(rhs)
        if not m or m.group(1) not in _MAJOR_OPS:
            continue
        lhs_shapes = rhs[: m.start()]
        for dt, dims in _SHAPES_RE.findall(lhs_shapes):
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += 2 * n * DTYPE_BYTES[dt]
    return total


def attention_correction_flops(cfg, shape) -> float:
    """Analytic attention-score FLOPs missing from the compiled count.

    The flash-attention custom-VJP iterates (q-chunk x kv-chunk) lax.scans;
    XLA cost analysis counts a while body ONCE, so the score/PV matmul FLOPs
    are under-reported by ~(nq*nk). The score FLOPs have a closed form —
    fwd = 4*B*H*T*S*D per layer (QK^T + PV) — which we add back with
    multipliers fwd=1 (prefill) or fwd+remat+bwd = 4+4+10 /4 = 4.5x (train).
    Decode paths don't scan (dense cached attention) and need no correction.
    Recorded separately in the §Roofline table as attn_corr_flops.
    """
    if shape.kind == "decode" or cfg.family == "ssm":
        return 0.0
    b, t = shape.global_batch, shape.seq_len
    # balanced causal scheme computes only the lower triangle in the forward:
    # fwd (and remat-fwd) score FLOPs halve; the backward is full-scheme.
    fwd = 0.5 if cfg.causal_scheme == "balanced" else 1.0
    mult = (4 * fwd + 4 * fwd + 10) / 4 if shape.kind == "train" else fwd
    d = cfg.head_dim
    h = cfg.num_heads
    total = 0.0
    if cfg.family in ("dense", "moe", "vlm"):
        tt = t + (cfg.num_patches if cfg.family == "vlm" else 0)
        total = cfg.num_layers * 4.0 * b * h * tt * tt * d
    elif cfg.family == "hybrid":
        g = cfg.num_layers // cfg.attn_every
        total = g * 4.0 * b * h * t * t * d
    elif cfg.family == "encdec":
        f = cfg.encoder_seq
        total = (
            cfg.encoder_layers * 4.0 * b * h * f * f * d      # encoder self
            + cfg.num_layers * 4.0 * b * h * t * t * d        # decoder self
            + cfg.num_layers * 4.0 * b * h * t * f * d        # cross
        )
    return total * mult


def model_flops(cfg, shape) -> float:
    """6 * N_active * D (dense) — the 'useful compute' yardstick."""
    params = model.abstract_params(cfg)
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    if cfg.num_experts:
        # active = non-expert params + top_k/E of expert params
        expert = sum(
            int(np.prod(l.shape))
            for path, l in jax.tree_util.tree_leaves_with_path(params)
            if "experts" in str(path)
        )
        total = (total - expert) + expert * cfg.top_k / cfg.num_experts
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * total * tokens


def _compile_cell(
    arch_id: str,
    shape_id: str,
    mesh,
    salaad: bool = True,
    cfg_overrides: dict | None = None,
    unroll: bool = True,
    accum_steps: int = 1,
):
    """Lower + compile one cell at one unroll setting. Returns compiled obj.

    Two compiles per cell (see run_cell): layer scans make XLA's cost
    analysis count the while-body ONCE (FLOPs off by num_layers), while full
    unrolling makes XLA:CPU's buffer assignment wildly overstate peak memory
    (120 GB vs 14.5 GB measured on olmo_1b train_4k). So: unrolled HLO is the
    FLOP/byte/collective ground truth, scanned HLO is the memory ground truth
    (and the program production actually runs).
    """
    import dataclasses

    cfg = get_arch(arch_id)
    cfg = dataclasses.replace(cfg, scan_unroll=unroll or 1, **(cfg_overrides or {}))
    shape = SHAPES[shape_id]

    params_abs = model.abstract_params(cfg)
    pshard = param_sharding_tree(params_abs, mesh)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params_abs))

    scfg = SalaadConfig(
        selection=SelectionConfig(),
        surrogate_dtype=jnp.bfloat16,
    ) if salaad else None

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state_abs = abstract_train_state(params_abs, scfg)
            blocks = select_blocks(params_abs, scfg.selection) if scfg else []
            state_shard = state_abs._replace(
                params=pshard,
                opt=state_abs.opt._replace(
                    mu=pshard, nu=pshard, count=NamedSharding(mesh, P())
                ),
                slr=slr_shardings(state_abs.slr, pshard, mesh),
                step=NamedSharding(mesh, P()),
            )
            specs = model.input_specs(cfg, shape)
            if accum_steps > 1:
                # pre-split microbatches on the host (see steps.py pre_split)
                specs = {
                    k: jax.ShapeDtypeStruct(
                        (accum_steps, v.shape[0] // accum_steps, *v.shape[1:]),
                        v.dtype,
                    )
                    for k, v in specs.items()
                }
                raw = batch_shardings(
                    {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype) for k, v in specs.items()},
                    mesh,
                )
                bshard = {
                    k: NamedSharding(mesh, P(None, *raw[k].spec))
                    for k in specs
                }
            else:
                bshard = batch_shardings(specs, mesh)
            step = make_train_step(
                cfg, blocks, AdamConfig(), accum_steps=accum_steps,
                pre_split=accum_steps > 1,
            )
            jitted = jax.jit(
                step,
                in_shardings=(state_shard, bshard),
                out_shardings=(state_shard, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_abs, specs)
        elif shape.kind == "prefill":
            specs = model.input_specs(cfg, shape)
            bshard = batch_shardings(specs, mesh)
            step = make_prefill_step(cfg, max_len=shape.seq_len)
            cache_abs = jax.eval_shape(step, params_abs, specs)[1]
            cshard = cache_shardings(cache_abs, mesh)
            jitted = jax.jit(
                step, in_shardings=(pshard, bshard), out_shardings=(None, cshard)
            )
            lowered = jitted.lower(params_abs, specs)
        else:  # decode
            b = shape.global_batch
            tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(cfg, b, shape.seq_len)
            )
            cshard = cache_shardings(cache_abs, mesh)
            tshard = batch_shardings({"tokens": tok}, mesh)["tokens"]
            step = make_decode_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, tshard, cshard),
                out_shardings=(None, cshard),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(params_abs, tok, cache_abs)

        compiled = lowered.compile()
    return compiled, cfg, shape


def run_cell(
    arch_id: str,
    shape_id: str,
    mesh,
    salaad: bool = True,
    verbose: bool = True,
    cfg_overrides: dict | None = None,
    accum_steps: int = 1,
):
    """Compile a cell twice (unrolled: costs; scanned: memory) and derive the
    roofline record."""
    t0 = time.time()
    multi_pod = "pod" in mesh.axis_names
    compiled_scan, cfg, shape = _compile_cell(
        arch_id, shape_id, mesh, salaad, cfg_overrides, unroll=False,
        accum_steps=accum_steps,
    )
    mem = compiled_scan.memory_analysis()

    n_dev = int(np.prod(list(mesh.shape.values())))
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(model.abstract_params(cfg))
    )

    def cost_from(c):
        cost = c.cost_analysis()
        hlo = c.as_text()
        coll = collective_bytes(hlo)
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_acc": float(cost.get("bytes accessed", 0.0)),
            "hbm": fusion_aware_hbm_bytes(hlo),
            **{f"coll/{k}": float(v) for k, v in coll.items()},
        }

    if multi_pod:
        # multi-pod pass criterion: compile success + fits; the cost table is
        # single-pod only (assignment §Roofline) — reuse the scanned compile.
        costs = cost_from(compiled_scan)
        cost_method = "scanned(multi-pod; costs not comparable)"
    elif cfg.num_layers <= 16:
        compiled, _, _ = _compile_cell(
            arch_id, shape_id, mesh, salaad, cfg_overrides, unroll=True,
            accum_steps=accum_steps,
        )
        costs = cost_from(compiled)
        cost_method = "full-unroll"
    else:
        # Deep stacks: full unroll takes >30 min/cell on this 1-CPU host.
        # Compile TWO shallow fully-unrolled variants and fit cost linearly in
        # depth — exact for homogeneous layer stacks (every assigned arch),
        # and still 100% derived from compiled artifacts.
        step = cfg.attn_every if cfg.attn_every else 4
        l1, l2 = step, 2 * step
        over = dict(cfg_overrides or {})
        c1, _, _ = _compile_cell(
            arch_id, shape_id, mesh, salaad, {**over, "num_layers": l1},
            unroll=True, accum_steps=accum_steps,
        )
        c2, _, _ = _compile_cell(
            arch_id, shape_id, mesh, salaad, {**over, "num_layers": l2},
            unroll=True, accum_steps=accum_steps,
        )
        k1, k2 = cost_from(c1), cost_from(c2)
        costs = {}
        for key in k1:
            slope = (k2[key] - k1[key]) / (l2 - l1)
            costs[key] = k1[key] + slope * (cfg.num_layers - l1)
        cost_method = f"two-point-depth-fit({l1},{l2})"

    if accum_steps > 1 and not multi_pod:
        # the microbatch lax.scan body is counted once by cost analysis;
        # nearly the whole step lives inside it, so scale by accum (the Adam
        # tail outside the loop is <1% of step cost — conservative upper).
        costs = {k: v * accum_steps for k, v in costs.items()}
        cost_method += f";x{accum_steps}-accum-loop"

    coll = {
        k.split("/", 1)[1]: v for k, v in costs.items() if k.startswith("coll/")
    }

    # cost_analysis and the HLO text describe the PER-DEVICE partitioned
    # program (verified against a hand-checked SPMD matmul) — no /n_dev here.
    flops = costs["flops"]
    bytes_acc = costs["bytes_acc"]
    hbm_bytes = costs["hbm"] + float(getattr(mem, "argument_size_in_bytes", 0) or 0)
    mf = model_flops(cfg, shape)
    attn_corr = attention_correction_flops(cfg, shape) / n_dev
    flops_corrected = flops + attn_corr
    compute_t = flops_corrected / PEAK_FLOPS
    memory_t = hbm_bytes / HBM_BW
    memory_t_upper = bytes_acc / HBM_BW
    coll_t = coll["total"] / ICI_BW
    dominant = max(
        [("compute", compute_t), ("memory", memory_t), ("collective", coll_t)],
        key=lambda kv: kv[1],
    )[0]
    rec = {
        "arch": arch_id,
        "shape": shape_id,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "devices": n_dev,
        "params": n_params,
        "hlo_flops": flops,
        "attn_corr_flops": attn_corr,
        "hlo_flops_corrected": flops_corrected,
        "hlo_bytes_unfused": bytes_acc,
        "hbm_bytes": hbm_bytes,
        "collective_bytes": coll,
        "compute_s": compute_t,
        "memory_s": memory_t,
        "memory_s_unfused_upper": memory_t_upper,
        "collective_s": coll_t,
        "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": (mf / n_dev) / flops_corrected if flops_corrected else 0.0,
        "peak_memory_per_device": getattr(mem, "temp_size_in_bytes", None),
        "argument_size": getattr(mem, "argument_size_in_bytes", None),
        "output_size": getattr(mem, "output_size_in_bytes", None),
        "compile_s": round(time.time() - t0, 1),
        "cost_method": cost_method,
        "accum_steps": accum_steps,
    }
    if verbose:
        print(json.dumps(rec, indent=None, default=str))
        sys.stdout.flush()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-salaad", action="store_true")
    ap.add_argument("--accum", type=int, default=1, help="microbatch accumulation")
    ap.add_argument("--scheme", default=None, help="causal_scheme override (balanced)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    cells = runnable_cells() if args.all else [(args.arch, args.shape)]
    records, failures = [], []
    for mesh in meshes:
        for arch_id, shape_id in cells:
            try:
                records.append(
                    run_cell(
                        arch_id, shape_id, mesh,
                        salaad=not args.no_salaad, accum_steps=args.accum,
                        cfg_overrides=(
                            {"causal_scheme": args.scheme} if args.scheme else None
                        ),
                    )
                )
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append((arch_id, shape_id, str(mesh.shape), str(e)[:200]))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"records": records, "failures": failures}, f, indent=1, default=str)
    print(f"\n=== {len(records)} cells compiled, {len(failures)} failures ===")
    for f in failures:
        print("FAIL:", f)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
