"""Fused SLR kernel (PR 7 tentpole) — one Pallas pass for low-rank + sparse.

Three layers of coverage, all in interpret mode so CPU CI exercises the
kernel bodies:

  1. kernel parity: fused vs the jnp oracle AND vs the separate
     lowrank+bsr calls it replaces, across dtypes, ranks (incl. r=0),
     occupancies (incl. empty S), decode/prefill row widths, ragged shapes,
     the stacked layer axis (incl. under ``lax.scan``), and the per-slot
     adapter axis (multi-tenant serving, PR 10);
  2. fast paths: the empty-S skip never launches a kernel, and decode-width
     row tiles don't pad small batches to 128;
  3. the ``fused`` deployment format: scan-stacked (never unrolled), forward
     parity with ``factored``, and greedy token streams bitwise-identical to
     ``factored`` across paged decode, chunked prefill, int8 KV pages,
     speculative decoding, and elastic tiers (the acceptance criteria).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, admm_update, init_slr_state
from repro.core.selection import SelectionConfig
from repro.kernels import ops, ref
from repro.kernels.bsr_matmul import bsr_from_dense
from repro.kernels.slr_matmul import (
    TILE_BYTES,
    BsrStack,
    fused_grid,
    row_tile,
    stack_bsr,
)
from repro.models import model as model_lib
from repro.serving.deployed import DeployedModel
from repro.serving.elastic import ModelBank
from repro.serving.engine import EngineConfig, PagedServingEngine
from repro.serving.slr_params import SLRLinear
from repro.serving.speculative import SpeculativeEngine

I = dict(interpret=True)
TOL = {jnp.float32: dict(atol=2e-3, rtol=2e-3), jnp.bfloat16: dict(atol=1e-1, rtol=1e-1)}


def rnd(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


def make_sparse(key, n, m, occupancy, bs, dtype=jnp.float32):
    """Block-sparse dense matrix with ~``occupancy`` live tiles (padded dims
    allowed: the trailing partial blocks are part of the live tiles)."""
    ib, jb = -(-n // bs), -(-m // bs)
    mask = jax.random.uniform(jax.random.PRNGKey(key + 77), (ib, jb)) < occupancy
    full = rnd(key, (ib * bs, jb * bs), dtype) * jnp.repeat(
        jnp.repeat(mask, bs, 0), bs, 1
    ).astype(dtype)
    return np.asarray(full[:n, :m], np.float32)


def sparse_with_counts(key, n, m, bs, counts, dtype=jnp.float32):
    """Dense S whose column window ``j`` holds ``counts[j]`` live blocks at
    scattered row blocks."""
    ib = -(-n // bs)
    rng = np.random.default_rng(key)
    s = np.zeros((ib * bs, len(counts) * bs), np.float32)
    for j, c in enumerate(counts):
        for i in rng.choice(ib, size=c, replace=False):
            s[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs] = rng.standard_normal(
                (bs, bs))
    return np.asarray(jnp.asarray(s[:n, :m]).astype(dtype), np.float32)


# Tables that need more than one P chunk and more than one slot group: 34 row
# blocks of 128 (MAXB 34, not a power of two: groups of 17) at rank 349 (not
# a multiple of 128). Per layer, the live blocks of each column window: a
# full column, one whose trailing group is dead, an empty one, one that ends
# inside its second group, one with a single live block.
GROUPED = dict(k=34 * 128, m=5 * 128, r=349, bs=128,
               counts=((34, 17, 0, 20, 1), (1, 34, 17, 0, 33)))


def grouped_tables(lead, x_dtype, seed=40):
    k, m, r, bs = GROUPED["k"], GROUPED["m"], GROUPED["r"], GROUPED["bs"]
    layers = GROUPED["counts"]
    p = rnd(seed + 1, (lead, k, r), x_dtype) / np.sqrt(k)
    vt = rnd(seed + 2, (lead, r, m), x_dtype)
    mats = [bsr_from_dense(sparse_with_counts(seed + l, k, m, bs,
                                              layers[l % len(layers)]), bs)
            for l in range(lead)]
    stack = stack_bsr(mats)
    g = fused_grid(8, stack, r, x_dtype=x_dtype, p_dtype=p.dtype)
    assert g.chunks > 1 and g.groups > 1 and g.maxb == 34, (g.chunks, g.groups)
    return p, vt, stack


def assert_close(got, want, dtype):
    got32, want32 = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want32).max()), 1.0)
    np.testing.assert_allclose(got32 / scale, want32 / scale, **TOL[dtype])


# ------------------------------------------------------------ kernel parity ---


class TestFusedKernelParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("t", [4, 128])          # decode / prefill widths
    @pytest.mark.parametrize("r", [0, 8])
    @pytest.mark.parametrize("occupancy", [0.0, 0.4, 1.0])
    def test_matrix(self, dtype, t, r, occupancy):
        k, m, bs = 96, 160, 32
        x = rnd(0, (t, k), dtype)
        p, vt = rnd(1, (k, r), dtype), rnd(2, (r, m), dtype)
        s = make_sparse(3, k, m, occupancy, bs)
        bsr = bsr_from_dense(s.astype(np.asarray(x).dtype), bs)
        got = ops.slr_matmul(x, p, vt, bsr, **I)
        assert got.shape == (t, m) and got.dtype == x.dtype
        assert_close(got, ref.slr_matmul_ref(x, p, vt, bsr), dtype)

    @pytest.mark.parametrize("t", [4, 128])
    def test_matches_separate_calls(self, t):
        """The fused pass replaces lowrank_matmul + bsr_matmul + XLA add."""
        k, m, r, bs = 128, 128, 16, 32
        x, p, vt = rnd(0, (t, k)), rnd(1, (k, r)), rnd(2, (r, m))
        bsr = bsr_from_dense(make_sparse(3, k, m, 0.5, bs), bs)
        fused = ops.slr_matmul(x, p, vt, bsr, **I)
        separate = ops.lowrank_matmul(x, p, vt, **I) + ops.bsr_matmul(x, bsr, **I)
        assert_close(fused, separate, jnp.float32)

    def test_ragged_shape_pads(self):
        """Satellite: odd hidden sizes deploy — trailing partial blocks are
        zero-padded, outputs sliced back (masked parity vs plain matmul)."""
        t, k, m, r, bs = 5, 72, 100, 4, 32
        x, p, vt = rnd(0, (t, k)), rnd(1, (k, r)), rnd(2, (r, m))
        s = make_sparse(3, k, m, 0.3, bs)
        bsr = bsr_from_dense(s, bs)
        assert bsr.shape == (k, m) and bsr.padded_shape == (96, 128)
        got = ops.slr_matmul(x, p, vt, bsr, **I)
        want = np.asarray(x) @ (np.asarray(p) @ np.asarray(vt) + s)
        assert_close(got, want, jnp.float32)

    def test_fully_truncated(self):
        """r = 0 AND empty S: y = x @ 0 without any kernel launch."""
        x = rnd(0, (8, 64))
        bsr = bsr_from_dense(np.zeros((64, 32), np.float32), 32)
        got = ops.slr_matmul(x, jnp.zeros((64, 0)), jnp.zeros((0, 32)), bsr, **I)
        np.testing.assert_array_equal(got, jnp.zeros((8, 32)))
        got = ops.slr_matmul(x, None, None, bsr, **I)
        np.testing.assert_array_equal(got, jnp.zeros((8, 32)))


class TestStackedKernel:
    def _stacked(self, num_l=3, k=64, m=128, r=8, bs=32):
        p, vt = rnd(1, (num_l, k, r)), rnd(2, (num_l, r, m))
        # per-layer occupancies including one all-empty layer inside a
        # non-empty stack — its counts row is all zero, the epilogue only
        # pays the per-column low-rank emit
        mats = [
            bsr_from_dense(make_sparse(10 + l, k, m, occ, bs), bs)
            for l, occ in enumerate((0.4, 0.0, 0.9))
        ]
        return p, vt, stack_bsr(mats)

    @pytest.mark.parametrize("case", ["mixed", "grouped_f32", "grouped_bf16"])
    def test_layers_match_per_matrix_oracle(self, case):
        """``mixed``: per-layer occupancies, one layer empty. ``grouped_*``:
        several P chunks and slot groups, counts below MAXB, dead trailing
        groups, rank 349 (``GROUPED``); bf16 activations and factors over
        float32 tables, as deployed."""
        if case == "mixed":
            p, vt, stack = self._stacked()
            x, dtype = rnd(0, (8, 64)), jnp.float32
        else:
            dtype = jnp.float32 if case == "grouped_f32" else jnp.bfloat16
            p, vt, stack = grouped_tables(2, dtype)
            x = rnd(0, (8, GROUPED["k"]), dtype)
        for l in range(stack.num_layers):
            got = ops.slr_matmul_stacked(x, p, vt, stack, jnp.int32(l), **I)
            want = ref.slr_matmul_stacked_ref(x, p, vt, stack, jnp.int32(l))
            assert got.dtype == x.dtype
            assert_close(got, want, dtype)

    def test_scannable_over_layers(self):
        """The whole point of the layer axis: the stack rides lax.scan."""
        p, vt, stack = self._stacked()
        x = rnd(0, (4, 64))

        def body(carry, l):
            return carry, ops.slr_matmul_stacked(carry, p, vt, stack, l, **I)

        _, ys = jax.lax.scan(body, x, jnp.arange(stack.num_layers))
        for l in range(stack.num_layers):
            assert_close(
                ys[l], ref.slr_matmul_stacked_ref(x, p, vt, stack, jnp.int32(l)),
                jnp.float32,
            )

    def test_stack_pads_to_common_maxb(self):
        _, _, stack = self._stacked()
        assert stack.rows.shape[0] == 3
        # layer 2 at 0.9 occupancy dictates MAXB; layer 1 is all padding
        assert int(np.max(np.asarray(stack.counts)[1])) == 0
        assert stack.rows.shape[2] == int(np.max(np.asarray(stack.counts)))

    def test_empty_stack_dispatches_lowrank(self):
        num_l, k, m, r = 2, 64, 64, 4
        p, vt = rnd(1, (num_l, k, r)), rnd(2, (num_l, r, m))
        mats = [bsr_from_dense(np.zeros((k, m), np.float32), 32)] * num_l
        stack = stack_bsr(mats)
        assert stack.empty
        got = ops.slr_matmul_stacked(rnd(0, (4, k)), p, vt, stack, jnp.int32(1), **I)
        want = ref.lowrank_matmul_ref(rnd(0, (4, k)), p[1], vt[1])
        assert_close(got, want, jnp.float32)


class TestMultiAdapterKernel:
    """The adapter axis (PR 10): slot ``b`` of the batch runs adapter
    ``ids[b]``'s tables, gathered inside the kernel's DMA index maps. The
    auto dispatch lowers to the jnp oracle off-TPU, so these tests force
    ``interpret=True`` to keep the emulated kernel body covered."""

    def _pool(self, n=4, k=64, m=128, r=8, bs=32):
        p, vt = rnd(1, (n, k, r)), rnd(2, (n, r, m))
        mats = [
            bsr_from_dense(make_sparse(20 + a, k, m, occ, bs), bs)
            for a, occ in enumerate((0.6, 0.0, 0.9, 0.3))
        ]
        return p, vt, stack_bsr(mats)

    @pytest.mark.parametrize("t", [1, 8])            # decode / prefill widths
    @pytest.mark.parametrize("pool", ["mixed", "grouped"])
    def test_slots_match_per_slot_oracle(self, pool, t):
        """``grouped``: adapters whose tables need several P chunks and slot
        groups (``GROUPED``)."""
        if pool == "mixed":
            p, vt, stack = self._pool()
        else:
            p, vt, stack = grouped_tables(4, jnp.float32)
        k, m = stack.shape
        x = rnd(0, (6, t, k))
        ids = jnp.asarray([2, 0, 3, 0, 1, 2], jnp.int32)   # repeats included
        got = ops.slr_matmul_multi(x, p, vt, stack, ids, **I)
        assert got.shape == (6, t, m) and got.dtype == x.dtype
        assert_close(got, ref.slr_matmul_multi_ref(x, p, vt, stack, ids),
                     jnp.float32)

    def test_slot_output_depends_only_on_its_id(self):
        """Permuting the slot->adapter map permutes rows, nothing else —
        the scalar-prefetch gather is truly per slot."""
        p, vt, stack = self._pool()
        x = rnd(0, (4, 1, 64))
        perm = np.asarray([3, 1, 0, 2])
        a = ops.slr_matmul_multi(x, p, vt, stack,
                                 jnp.asarray([0, 1, 2, 3]), **I)
        b = ops.slr_matmul_multi(x[perm], p, vt, stack,
                                 jnp.asarray(perm, jnp.int32), **I)
        assert_close(b, np.asarray(a)[perm], jnp.float32)

    def test_empty_pool_dispatches_lowrank_per_slot(self):
        n, k, m, r = 3, 64, 64, 4
        p, vt = rnd(1, (n, k, r)), rnd(2, (n, r, m))
        stack = stack_bsr([bsr_from_dense(np.zeros((k, m), np.float32), 32)] * n)
        assert stack.empty
        x, ids = rnd(0, (2, 4, k)), jnp.asarray([2, 0], jnp.int32)
        got = ops.slr_matmul_multi(x, p, vt, stack, ids, **I)
        assert_close(got, ref.slr_matmul_multi_ref(x, p, vt, stack, ids),
                     jnp.float32)

    def test_rank_zero_pool(self):
        p, vt, stack = self._pool(r=0)
        x, ids = rnd(0, (3, 1, 64)), jnp.asarray([1, 3, 0], jnp.int32)
        got = ops.slr_matmul_multi(x, p, vt, stack, ids, **I)
        assert_close(got, ref.slr_matmul_multi_ref(x, p, vt, stack, ids),
                     jnp.float32)

    def test_auto_dispatch_is_the_oracle_off_tpu(self, monkeypatch):
        """Interpret-mode grid emulation charges every call for the FULL
        pooled operands (cost grows with pool capacity, not batch), so the
        non-TPU lowering is the vectorized oracle; explicit interpret=True
        still reaches the kernel (the tests above depend on it)."""
        import repro.kernels.ops as ops_mod

        assert ops_mod._auto_interpret()            # this container: no TPU
        monkeypatch.setattr(
            ops_mod, "slr_matmul_multi_pallas",
            lambda *a, **k: pytest.fail("emulated kernel in auto dispatch"),
        )
        p, vt, stack = self._pool()
        x, ids = rnd(0, (2, 1, 64)), jnp.asarray([1, 2], jnp.int32)
        got = ops.slr_matmul_multi(x, p, vt, stack, ids)
        assert_close(got, ref.slr_matmul_multi_ref(x, p, vt, stack, ids),
                     jnp.float32)


class TestGridArithmetic:
    """The fused kernel's grid at OLMo-1B's sites, from shapes alone: every
    block of S live (MAXB = K blocks), bf16 activations and factors, float32
    block tables; no kernel runs."""

    @pytest.mark.parametrize("width", [8, 128])
    @pytest.mark.parametrize("r", [512, 349])
    @pytest.mark.parametrize("site", [(2048, 2048), (2048, 8192), (8192, 2048)])
    def test_olmo_sites(self, site, r, width):
        n, m = site
        bs, layers = 128, 16
        k_tiles, jb = n // bs, m // bs
        sds = jax.ShapeDtypeStruct
        stack = BsrStack(
            sds((layers, jb), jnp.int32), sds((layers, jb, k_tiles), jnp.int32),
            sds((layers, jb, k_tiles, bs, bs), jnp.float32), (n, m), bs)
        g = fused_grid(width, stack, r, x_dtype=jnp.bfloat16,
                       p_dtype=jnp.bfloat16)
        one_block_per_step = k_tiles + jb * k_tiles
        assert g.steps_per_row_tile * 10 <= one_block_per_step
        assert g.grid == (-(-width // g.bt), g.chunks + jb * g.groups)
        assert k_tiles % g.chunk == 0 and k_tiles % g.group == 0
        assert all(b <= TILE_BYTES for b in g.tile_bytes.values())
        assert g.vmem_bytes <= g.vmem_limit_bytes

    def test_olmo_decode_program(self):
        """One layer of OLMo-1B (q, k, v, o; gate, up; down) at r 512 runs
        234 grid steps a row tile, where one block a step ran 4,256."""
        sds = jax.ShapeDtypeStruct
        steps = 0
        for n, m in [(2048, 2048)] * 4 + [(2048, 8192)] * 2 + [(8192, 2048)]:
            kt, jb = n // 128, m // 128
            stack = BsrStack(None, sds((1, jb, kt), jnp.int32),
                             sds((1, jb, kt, 128, 128), jnp.float32), (n, m), 128)
            steps += fused_grid(8, stack, 512, x_dtype=jnp.bfloat16,
                                p_dtype=jnp.bfloat16).steps
        assert steps == 4 * 17 + 2 * 65 + 36 == 234


# ---------------------------------------------------------------- fast paths ---


class TestFastPaths:
    def test_empty_s_skips_bsr_kernel(self, monkeypatch):
        """ops.bsr_matmul must not launch a kernel for a statically-empty S."""
        import repro.kernels.ops as ops_mod

        monkeypatch.setattr(
            ops_mod, "bsr_matmul_pallas",
            lambda *a, **k: pytest.fail("kernel launched for empty S"),
        )
        bsr = bsr_from_dense(np.zeros((64, 32), np.float32), 32)
        assert bsr.empty
        out = ops.bsr_matmul(rnd(0, (8, 64)), bsr)
        np.testing.assert_array_equal(out, jnp.zeros((8, 32)))

    def test_empty_s_skips_fused_sparse_epilogue(self, monkeypatch):
        """The fused wrapper drops to the low-rank-only kernel for empty S."""
        import repro.kernels.ops as ops_mod

        monkeypatch.setattr(
            ops_mod, "slr_matmul_pallas",
            lambda *a, **k: pytest.fail("fused kernel launched for empty S"),
        )
        x, p, vt = rnd(0, (8, 64)), rnd(1, (64, 4)), rnd(2, (4, 32))
        bsr = bsr_from_dense(np.zeros((64, 32), np.float32), 32)
        got = ops.slr_matmul(x, p, vt, bsr, **I)
        assert_close(got, ref.lowrank_matmul_ref(x, p, vt), jnp.float32)

    def test_decode_width_row_tiles(self):
        assert row_tile(4, jnp.float32) == 8      # not 128
        assert row_tile(4, jnp.bfloat16) == 16
        assert row_tile(100, jnp.float32) == 104
        assert row_tile(300, jnp.float32) == 128  # capped for prefill


# ------------------------------------------------------------- fused format ---


@pytest.fixture(scope="module")
def trained():
    cfg = get_arch("olmo_1b").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    scfg = SalaadConfig(
        selection=SelectionConfig(min_dim=16), rho_constant=5.0, exact_svd=True
    )
    state, blocks = init_slr_state(params, scfg)
    for step in range(4):
        state, _ = admm_update(params, state, blocks, scfg, step)
    return cfg, params, state, blocks


@pytest.fixture(scope="module")
def banks(trained):
    cfg, params, state, blocks = trained
    return {
        fmt: ModelBank.build(cfg, params, state, blocks, budgets=(1.0, 0.6),
                             fmt=fmt, bsr_block=32)
        for fmt in ("factored", "fused")
    }


PROMPTS = [[5, 7, 11, 13, 17], [23, 29, 31, 37, 41, 43, 47, 53, 59], [61, 67, 71]]


def run_tokens(engine, prompts, max_new=5, tiers=None):
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=max_new,
                      tier=None if tiers is None else tiers[i])
    return {r.uid: r.out_tokens for r in engine.run()}


class TestFusedFormat:
    def test_layers_stay_scan_stacked(self, trained):
        """Unlike 'bsr', 'fused' never unrolls the layer stack — the stacked
        tables scan by index through the kernel's scalar-prefetch maps."""
        cfg, params, state, blocks = trained
        dm = DeployedModel.build(cfg, params, state, blocks, fmt="fused",
                                 bsr_block=32)
        assert not isinstance(dm.params["layers"], (list, tuple))
        is_slr = lambda x: isinstance(x, SLRLinear)  # noqa: E731
        stacked = [
            leaf for leaf in jax.tree_util.tree_leaves(
                dm.params["layers"], is_leaf=is_slr)
            if isinstance(leaf, SLRLinear)
        ]
        assert stacked and any(l.scan_by_index for l in stacked)
        assert all(l.fuse for l in stacked)

    def test_forward_parity_vs_factored(self, trained):
        cfg, params, state, blocks = trained
        dm_fa = DeployedModel.build(cfg, params, state, blocks, fmt="factored")
        dm_fu = DeployedModel.build(cfg, params, state, blocks, fmt="fused",
                                    bsr_block=32)
        toks = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6], [5, 3, 5, 8, 9, 7, 9, 3]],
                           jnp.int32)
        lf, lu = dm_fa.forward(toks), dm_fu.forward(toks)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lu),
                                   atol=2e-3, rtol=2e-3)
        assert bool((lf.argmax(-1) == lu.argmax(-1)).all())

    def test_bank_reports_kernel_grid(self, banks):
        """Each fused tier records its kernel grid at build: the report and
        the engine's gauges carry the steps of a decode program and the live
        share of the S tiles it streams."""
        bank = banks["fused"]
        for tier in bank:
            plan = tier.slr_kernel
            assert plan is not None and plan.calls > 0
            assert 0.0 < plan.live_tile_share <= 1.0
            assert plan.live_slots <= plan.fetched_slots
            assert plan.grid_steps(300) == 3 * plan.steps_per_row_tile
        rep = bank.report(widths=(3,))
        assert [t["slr_kernel"]["grid_steps"] for t in rep["tiers"]] == [
            {3: t.slr_kernel.grid_steps(3)} for t in bank]
        assert banks["factored"][0].slr_kernel is None
        eng = PagedServingEngine(bank, EngineConfig(max_slots=3, max_len=32,
                                                    block_size=8))
        gauges = eng.stats_snapshot()["metrics"]
        steps = gauges["serve_slr_grid_steps"]["values"]
        share = gauges["serve_slr_live_tile_share"]["values"]
        for tier in bank:
            key = f"PagedServingEngine,{tier.index}"
            assert steps[f"{key},3"] == tier.slr_kernel.grid_steps(3)
            assert share[key] == tier.slr_kernel.live_tile_share

    def test_param_bytes_accounts_stacked_tables(self, trained):
        cfg, params, state, blocks = trained
        dm = DeployedModel.build(cfg, params, state, blocks, fmt="fused",
                                 bsr_block=32)
        acct = dm.param_bytes()
        assert acct["format"] == "fused" and acct["structured_bytes"] > 0


class TestFusedEngineStreams:
    """Acceptance: fused greedy streams bitwise-identical to factored."""

    def _compare(self, banks, engine_cls, ecfg_kw, max_new=5, tiers=None):
        streams = {}
        for fmt in ("factored", "fused"):
            eng = engine_cls(banks[fmt], EngineConfig(**ecfg_kw))
            streams[fmt] = run_tokens(eng, PROMPTS, max_new=max_new, tiers=tiers)
        assert streams["fused"] == streams["factored"], streams
        return streams["fused"]

    def test_paged_decode(self, banks):
        out = self._compare(
            banks, PagedServingEngine,
            dict(max_slots=3, max_len=32, block_size=8),
        )
        assert all(len(t) == 5 for t in out.values())

    def test_chunked_prefill(self, banks):
        self._compare(
            banks, PagedServingEngine,
            dict(max_slots=3, max_len=64, block_size=8, prefill_chunk=8),
        )

    def test_int8_kv_pages(self, banks):
        self._compare(
            banks, PagedServingEngine,
            dict(max_slots=3, max_len=32, block_size=8, kv_dtype="int8"),
        )

    def test_speculative(self, banks):
        self._compare(
            banks, SpeculativeEngine,
            dict(max_slots=2, max_len=32, block_size=8, spec_k=3),
        )

    def test_elastic_tiers(self, banks):
        """Per-request tiers: tier-1 slots ride the 0.6-budget fused weights
        and still match factored token-for-token."""
        self._compare(
            banks, PagedServingEngine,
            dict(max_slots=3, max_len=32, block_size=8),
            tiers=[0, 1, 1],
        )
