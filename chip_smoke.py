"""Bring-up smoke test: SALAAD's train-then-serve path on one TPU chip.

Runs ``salaad_llama_60m`` at its published widths (d_model 512, 8 layers,
8 heads of 64, d_ff 1376, vocabulary 32000, float32) from random weights made
from ``--seed``, through the entry points a user calls:

1. train: a few SALAAD steps through ``train.trainer.Trainer`` (the class
   ``launch/train.py`` drives) on ``SyntheticC4``, with ADMM stage-2 updates
   every second step, so every served site holds live rank and sparse
   entries (a fresh SLR state is all zeros and would skip the kernels);
2. serve: three HPA budget tiers deployed in the ``fused`` format with Pallas
   attention, one ``ModelBank``, 8 requests through ``PagedServingEngine``
   (a warm-up pass, then a timed pass that must not retrace), checked
   against a float32 ``jax.numpy`` reference.

``--four-chip`` runs only the paths that exist across chips, each against its
one-chip counterpart: the paged engine at ``mesh="model=4"`` and one sharded
training step on a (data=2, model=2) mesh.

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``. Without a TPU the script exits non-zero
before any phase runs, and a failed check raises. All work happens in this
one process, which holds the chip.

    python chip_smoke.py [--seed 0] [--four-chip]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_arch  # noqa: E402
from repro.core.admm import SalaadConfig  # noqa: E402
from repro.core.hpa import hpa_keep_ratio  # noqa: E402
from repro.core.selection import SelectionConfig  # noqa: E402
from repro.data.synthetic import DataConfig, SyntheticC4  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.optim.adam import AdamConfig  # noqa: E402
from repro.serving.deployed import DeployedModel, is_linear_site  # noqa: E402
from repro.serving.elastic import ModelBank  # noqa: E402
from repro.serving.engine import EngineConfig, PagedServingEngine  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

ARCH = "salaad_llama_60m"
KEEPS = (1.0, 0.6, 0.3)           # HPA budget tiers, largest first
KAPPA = 0.7                       # low-rank share of each HPA budget
PROMPT_LENS = (200, 16, 48, 120, 180, 96, 33, 150)
MAX_NEW = 16
PAGE = 16                         # KV page size in tokens
MAX_LEN = 256                     # per-request cache capacity
# Tier-0 first-step logits against the float32 reference, as shares of the
# reference's largest |logit| (CHANGES.md gives the reasons): the
# served path computed at float32 precision, and as the engine ran it, at
# the backend's default matmul precision (one bfloat16 pass on a TPU).
# Greedy tokens must agree wherever the reference's top-two margin is wider
# than the default-precision tolerance.
LOGIT_RTOL_F32 = 2.0 ** -12
LOGIT_RTOL_DEFAULT = 2.0 ** -5
SALAAD = SalaadConfig(selection=SelectionConfig(min_dim=16), update_every=2)


class SmokeFailure(AssertionError):
    """A check on the chip's results failed."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def emit(**fields):
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache reads
    included) and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def timed(self, phase: str, run):
        """``run()``, then one line with its wall and compile seconds."""
        s0, h0, t0 = self.seconds, self.cache_hits, time.perf_counter()
        out = run()
        emit(phase=f"{phase}_timing", wall_s=time.perf_counter() - t0,
             compile_s=self.seconds - s0, cache_hits=self.cache_hits - h0)
        return out


def served_site_stats(slr, blocks) -> dict:
    """Smallest live rank and sparse-entry count over the linear sites the
    deployment serves structured (stacked sites: the largest over layers,
    which is what the stacked tables keep)."""
    ranks, nnz = [], []
    for info in blocks:
        if not is_linear_site(info):
            continue
        blk = slr[info.name]
        ranks.append(int((np.asarray(blk.s_vals) > 0).sum(axis=-1).max()))
        nnz.append(int((np.asarray(blk.s_coo.idx) >= 0).sum()))
    return {"sites": len(ranks), "min_rank": min(ranks), "min_nnz": min(nnz)}


def train_phase(cfg, *, seed: int, steps: int = 6, seq_len: int = 256,
                batch: int = 8):
    """SALAAD training through ``Trainer``; returns (trainer, state)."""
    trainer = Trainer(cfg, TrainerConfig(total_steps=steps, log_every=1,
                                         salaad=SALAAD))
    data = SyntheticC4(DataConfig(cfg.vocab_size, seq_len, batch, seed=seed))
    t0 = time.perf_counter()
    state = trainer.init(jax.random.PRNGKey(seed))
    state = trainer.fit(state, data)
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    losses = [m["loss"] for m in trainer.metrics_log if "loss" in m]
    admm = [m for m in trainer.metrics_log if "admm_recon_err" in m]
    sites = served_site_stats(state.slr, trainer.blocks)
    emit(phase="train", steps=steps, seq_len=seq_len, batch=batch,
         losses=losses, admm_updates=len(admm),
         admm_recon_err=[m["admm_recon_err"] for m in admm],
         step_s=[m["sec"] for m in trainer.metrics_log if "sec" in m],
         wall_s=wall, **sites)
    check(len(losses) == steps and all(np.isfinite(losses)),
          f"non-finite or missing losses {losses}")
    check(len(admm) >= 2, f"{len(admm)} ADMM stage-2 updates, want >= 2")
    check(sites["min_rank"] > 0 and sites["min_nnz"] > 0,
          f"a served site has no live rank or no sparse entries: {sites}")
    return trainer, state


def make_prompts(vocab: int, lens, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def drive(engine, prompts, tiers: int, max_new: int) -> list:
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=max_new, tier=i % tiers)
    return sorted(engine.run(), key=lambda r: r.uid)


def pad_rows(rows: list[list[int]], width: int) -> np.ndarray:
    out = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def forward_logits(cfg, params, rows: list[list[int]], width: int) -> np.ndarray:
    """Full-forward logits of token rows zero-padded to ``width`` (causal, so
    the padding never reaches a position inside a row)."""
    fwd = jax.jit(lambda p, t: model_lib._forward(p, {"tokens": t}, cfg)[0])
    return np.asarray(fwd(params, jnp.asarray(pad_rows(rows, width))), np.float32)


def serve_phase(cfg, params, slr, blocks, *, interpret: bool, seed: int,
                prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW,
                max_len: int = MAX_LEN) -> dict:
    """Serve the HPA tiers as fused deployments with Pallas attention and
    check the results; ``interpret`` names the Pallas mode (False on the
    chip, True for a CPU rehearsal)."""
    pcfg = dataclasses.replace(cfg, kernel_impl="pallas")
    prompts = make_prompts(cfg.vocab_size, prompt_lens, seed)
    with ops.interpret_mode(interpret):
        tiers = [hpa_keep_ratio(slr, blocks, k, KAPPA)[0] for k in KEEPS]
        models = [DeployedModel.build(pcfg, params, t, blocks, fmt="fused")
                  for t in tiers]
        bank = ModelBank(pcfg, models, keeps=KEEPS)
        engine = PagedServingEngine(bank, EngineConfig(
            max_slots=len(prompts), max_len=max_len, block_size=PAGE))
        t0 = time.perf_counter()
        warm = drive(engine, prompts, len(KEEPS), max_new)
        warm_s = time.perf_counter() - t0
        before = engine.stats_snapshot()["jit_retraces"]
        t0 = time.perf_counter()
        done = drive(engine, prompts, len(KEEPS), max_new)
        timed_s = time.perf_counter() - t0
        retraces = engine.stats_snapshot()["jit_retraces"] - before
        decode_hlo = engine.lower_decode(0).compile().as_text()
        # tier 0's first generated token comes from the prefill forward's
        # logits at the last prompt position
        tier0 = [r for r in done if r.tier == 0]
        prompts0 = [r.prompt for r in tier0]
        served = forward_logits(pcfg, models[0].params, prompts0, max_len)
        with jax.default_matmul_precision("highest"):
            served_f32 = forward_logits(pcfg, models[0].params, prompts0,
                                        max_len)

    # float32 reference: L + S materialized, jnp attention, full precision;
    # teacher-forced on the served streams so every step of every stream is
    # judged against the reference's own logits
    ref_model = DeployedModel.build(cfg, params, tiers[0], blocks, fmt="dense")
    streams = [r.prompt + r.out_tokens[:-1] for r in tier0]
    steps = [list(range(len(r.prompt) - 1, len(s))) for r, s in zip(tier0, streams)]
    with jax.default_matmul_precision("highest"):
        ref = forward_logits(cfg, ref_model.params, streams, max_len)

    def first_step(logits):
        return np.stack([logits[i, s[0]] for i, s in enumerate(steps)])

    ref_first = first_step(ref)
    scale = float(np.abs(ref_first).max())
    tol_f32, tol = LOGIT_RTOL_F32 * scale, LOGIT_RTOL_DEFAULT * scale
    errs = {"served_f32": first_step(served_f32), "served": first_step(served)}
    # what lower precisions cost the reference itself on the same inputs: the
    # backend's default matmul precision, and bfloat16 weights
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), ref_model.params)
    errs["ref_default_precision"] = first_step(
        forward_logits(cfg, ref_model.params, prompts0, max_len))
    errs["ref_bf16_weights"] = first_step(forward_logits(
        dataclasses.replace(cfg, param_dtype=jnp.bfloat16), bf16, prompts0,
        max_len))
    errs = {k: float(np.abs(v - ref_first).max()) for k, v in errs.items()}
    exact = ties = disagree = 0
    for i, (r, s) in enumerate(zip(tier0, steps)):
        for j, pos in enumerate(s):
            row = ref[i, pos]
            top2 = np.sort(row)[-2:]
            if r.out_tokens[j] == int(np.argmax(row)):
                exact += 1
            elif top2[1] - top2[0] <= tol:
                ties += 1        # a near-tie: either token is within tolerance
            else:
                disagree += 1

    tokens = sum(len(r.out_tokens) for r in done)
    report = dict(
        phase="serve", fmt="fused", kernel_impl="pallas", interpret=interpret,
        tiers=list(KEEPS), requests=len(prompts), completed=len(done),
        prompt_lens=list(prompt_lens), max_new=max_new, page=PAGE,
        tokens=tokens, warmup_s=warm_s, timed_s=timed_s,
        timed_tok_per_s=tokens / timed_s, jit_retraces_timed=retraces,
        decode_tpu_custom_calls=decode_hlo.count("tpu_custom_call"),
        streams_repeat=[r.out_tokens for r in warm] == [r.out_tokens for r in done],
        tier0_requests=len(tier0), logits_scale=scale,
        logits_tol_f32=tol_f32, logits_tol_default=tol,
        **{f"logits_err_{k}": v for k, v in errs.items()}, greedy_exact=exact,
        greedy_near_ties=ties, greedy_disagree=disagree,
    )
    emit(**report)
    check(len(done) == len(prompts) and all(
        len(r.out_tokens) == max_new for r in done), "a request did not complete")
    check(retraces == 0, f"{retraces} jit retraces in the timed pass")
    check(interpret or report["decode_tpu_custom_calls"] > 0,
          "the decode program holds no Pallas kernel")
    check(errs["served_f32"] <= tol_f32,
          f"tier-0 float32 logits off the reference by {errs['served_f32']}")
    check(errs["served"] <= tol,
          f"tier-0 served logits off the reference by {errs['served']}")
    check(disagree == 0 and exact > 0,
          f"greedy tokens disagree with the reference at {disagree} steps")
    return report


def four_chip_phase(cfg, *, seed: int, seq_len: int = 256, batch: int = 8):
    """Tensor-parallel serving and the sharded training step, each against
    its one-chip counterpart."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.dryrun import slr_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import batch_shardings, param_sharding_tree
    from repro.train.state import init_train_state
    from repro.train.steps import make_admm_step, make_train_step

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, want 4")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    state, blocks = init_train_state(params, SALAAD)
    step = make_train_step(cfg, blocks, AdamConfig())
    data = SyntheticC4(DataConfig(cfg.vocab_size, seq_len, batch, seed=seed))
    batch0 = data.batch(0)
    one, m1 = jax.jit(step)(state, batch0)
    mesh = make_host_mesh(data=2, model=2)
    pshard = param_sharding_tree(state.params, mesh)
    whole = NamedSharding(mesh, P())
    sharded = jax.device_put(state, state._replace(
        params=pshard,
        opt=state.opt._replace(mu=pshard, nu=pshard, count=whole),
        slr=slr_shardings(state.slr, pshard, mesh), step=whole,
    ))
    with jax.set_mesh(mesh):
        _, m4 = jax.jit(step)(sharded, jax.device_put(
            batch0, batch_shardings(batch0, mesh)))
    loss1, loss4 = float(m1["loss"]), float(m4["loss"])
    emit(phase="train_sharded", mesh={"data": 2, "model": 2},
         loss_one_chip=loss1, loss_sharded=loss4, abs_diff=abs(loss1 - loss4))
    # the repository's own bound for this comparison (test_distributed)
    check(abs(loss1 - loss4) < 2e-3, f"sharded loss {loss4} != {loss1}")

    one, _ = jax.jit(make_admm_step(SALAAD, blocks))(one)
    pcfg = dataclasses.replace(cfg, kernel_impl="pallas")
    # one tier: each tier is one more set of programs to compile on 4 chips
    bank = ModelBank.build(pcfg, one.params, one.slr, blocks,
                           budgets=KEEPS[:1], fmt="factored")
    prompts = make_prompts(cfg.vocab_size, PROMPT_LENS, seed)
    streams, retraces = {}, {}
    for mesh_spec in (None, "model=4"):
        engine = PagedServingEngine(bank, EngineConfig(
            max_slots=len(prompts), max_len=MAX_LEN, block_size=PAGE,
            mesh=mesh_spec))
        done = drive(engine, prompts, 1, MAX_NEW)
        streams[mesh_spec] = [r.out_tokens for r in done]
        retraces[mesh_spec] = engine.stats_snapshot()["jit_retraces"]
    same = streams[None] == streams["model=4"]
    emit(phase="serve_tensor_parallel", fmt="factored", kernel_impl="pallas",
         mesh="model=4", requests=len(prompts),
         tokens=sum(map(len, streams["model=4"])), streams_equal=same,
         differing_streams=sum(a != b for a, b in
                               zip(streams[None], streams["model=4"])),
         jit_retraces=retraces["model=4"])
    check(same, "tensor-parallel streams differ from the one-chip streams")
    check(retraces["model=4"] == 0, "the tensor-parallel engine retraced")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip tensor-parallel paths")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend is {backend!r}",
              file=sys.stderr)
        return 1
    clock = CompileClock().install()
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cfg = get_arch(ARCH)
    emit(phase="setup", device=device, arch=ARCH, d_model=cfg.d_model,
         num_layers=cfg.num_layers, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
         param_dtype=str(jnp.dtype(cfg.param_dtype)), seed=args.seed,
         compile_cache=cache_dir)

    t0, s0 = time.perf_counter(), clock.seconds
    if args.four_chip:
        clock.timed("four_chip", lambda: four_chip_phase(cfg, seed=args.seed))
    else:
        trainer, state = clock.timed(
            "train", lambda: train_phase(cfg, seed=args.seed))
        clock.timed("serve", lambda: serve_phase(
            cfg, state.params, state.slr, trainer.blocks, interpret=False,
            seed=args.seed))
    emit(phase="total", wall_s=time.perf_counter() - t0,
         compile_s=clock.seconds - s0, cache_hits=clock.cache_hits)
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
