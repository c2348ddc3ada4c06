"""Tests for the two-stage ADMM (Algorithm 1), rSVD, controller, selection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparse
from repro.core.admm import (
    SalaadConfig,
    admm_update,
    init_slr_state,
    penalty,
    slr_param_count,
    surrogate_params,
)
from repro.core.controller import ControllerConfig, controller_update
from repro.core.rsvd import randomized_svd, rank_cap
from repro.core.scaling import rho_for_block
from repro.core.selection import SelectionConfig, select_blocks, total_logical_blocks


def make_slr_matrix(key, n, m, rank, dens, noise=0.0):
    ku, kv, ks, kn = jax.random.split(key, 4)
    u = jax.random.normal(ku, (n, rank)) / np.sqrt(rank)
    v = jax.random.normal(kv, (rank, m))
    s = jnp.where(jax.random.uniform(ks, (n, m)) < dens, 2.0, 0.0)
    x = u @ v + s
    if noise:
        x = x + noise * jax.random.normal(kn, (n, m))
    return x


class TestRSVD:
    @pytest.mark.parametrize("n,m,rank", [(64, 48, 8), (48, 64, 8), (128, 128, 16)])
    def test_matches_exact_on_lowrank(self, n, m, rank):
        key = jax.random.PRNGKey(0)
        u = jax.random.normal(key, (n, rank))
        v = jax.random.normal(jax.random.fold_in(key, 1), (rank, m))
        a = u @ v
        uu, s, vt = randomized_svd(a, jax.random.PRNGKey(42), rank + 4, n_iter=2)
        s_exact = jnp.linalg.svd(a, compute_uv=False)
        np.testing.assert_allclose(s[:rank], s_exact[:rank], rtol=2e-3)
        np.testing.assert_allclose(
            (uu * s[None]) @ vt, a, atol=2e-2 * float(jnp.abs(a).max())
        )

    def test_top_spectrum_accuracy_noisy(self):
        """rSVD top singular values of a noisy SLR matrix within 2% of exact."""
        a = make_slr_matrix(jax.random.PRNGKey(3), 96, 80, 6, 0.05, noise=0.01)
        _, s, _ = randomized_svd(a, jax.random.PRNGKey(0), 24, n_iter=2)
        s_exact = jnp.linalg.svd(a, compute_uv=False)
        np.testing.assert_allclose(s[:6], s_exact[:6], rtol=0.02)

    def test_deterministic_in_key(self):
        a = jax.random.normal(jax.random.PRNGKey(9), (32, 32))
        r1 = randomized_svd(a, jax.random.PRNGKey(5), 8)
        r2 = randomized_svd(a, jax.random.PRNGKey(5), 8)
        for x, y in zip(r1, r2):
            np.testing.assert_array_equal(x, y)

    def test_rank_cap_alignment(self):
        assert rank_cap(8192, 8192) == 2048  # 0.25*8192, already 128-aligned
        assert rank_cap(1000, 1000) % 1 == 0
        assert rank_cap(1000, 1000, 0.25) == min(256, 1000)  # 250 -> 256 aligned
        assert rank_cap(16, 16) == 8  # floor at minimum
        assert rank_cap(4, 4) == 4  # never exceeds min(n, m)


class TestSelection:
    def params(self):
        z = jnp.zeros
        return {
            "embed": {"embedding": z((256, 32))},
            "layers": {
                "q": z((4, 32, 32)),          # scan-stacked
                "experts": {"w1": z((4, 8, 32, 64))},  # stacked layers x experts
                "norm_scale": z((32,)),
                "tiny": z((4, 4)),            # below min_dim
            },
            "lm_head": {"w": z((32, 256))},
        }

    def test_default_selection(self):
        blocks = select_blocks(self.params(), SelectionConfig(min_dim=16))
        names = [b.name for b in blocks]
        assert "embed/embedding" in names
        assert "layers/q" in names
        assert "layers/experts/w1" in names
        assert all("lm_head" not in n for n in names)  # App. H: excluded
        assert all("norm" not in n for n in names)

    def test_lm_head_opt_in(self):
        blocks = select_blocks(
            self.params(), SelectionConfig(min_dim=16, include_lm_head=True)
        )
        assert any("lm_head" in b.name for b in blocks)

    def test_embedding_opt_out(self):
        blocks = select_blocks(
            self.params(), SelectionConfig(min_dim=16, include_embedding=False)
        )
        assert all("embed" not in b.name for b in blocks)

    def test_stack_dims_and_logical_count(self):
        blocks = select_blocks(self.params(), SelectionConfig(min_dim=16))
        by = {b.name: b for b in blocks}
        assert by["layers/q"].stack_dims == (4,)
        assert by["layers/experts/w1"].stack_dims == (4, 8)
        assert total_logical_blocks(blocks) == 1 + 4 + 32

    def test_rho_uses_logical_count(self):
        assert rho_for_block(64, 64, 10) == pytest.approx(
            2 * rho_for_block(64, 64, 20)
        )
        assert rho_for_block(64, 256, 10) == pytest.approx(
            rho_for_block(128, 128, 10)
        )  # depends only on sqrt(nm)


class TestController:
    def test_pushes_toward_target(self):
        cfg = ControllerConfig(target_rank_ratio=0.15, target_density=0.05)
        a, b = controller_update(
            jnp.zeros(()), jnp.zeros(()), jnp.array(0.5), jnp.array(0.5), 1.0, cfg
        )
        assert a > 0 and b > 0  # over target -> raise thresholds
        a2, b2 = controller_update(a, b, jnp.array(0.01), jnp.array(0.0), 1.0, cfg)
        assert a2 < a and b2 < b  # under target -> relax

    def test_nonnegative_clamp(self):
        cfg = ControllerConfig()
        a, b = controller_update(
            jnp.zeros(()), jnp.zeros(()), jnp.array(0.0), jnp.array(0.0), 1.0, cfg
        )
        assert a == 0 and b == 0

    def test_blockwise_independence(self):
        cfg = ControllerConfig()
        rr = jnp.array([0.5, 0.1])
        dd = jnp.array([0.5, 0.01])
        a, b = controller_update(jnp.zeros(2), jnp.zeros(2), rr, dd, 1.0, cfg)
        assert a[0] > a[1] and b[0] > b[1]


def tiny_params(key):
    x1 = make_slr_matrix(jax.random.fold_in(key, 0), 48, 40, 4, 0.05)
    x2 = jnp.stack(
        [make_slr_matrix(jax.random.fold_in(key, i + 1), 40, 48, 4, 0.05) for i in range(3)]
    )
    return {"embed": {"embedding": x1}, "layers": {"proj": x2}}


class TestADMMCycle:
    def setup_method(self):
        self.cfg = SalaadConfig(
            selection=SelectionConfig(min_dim=16),
            rho_constant=10.0,  # small matrices need a stronger pull
            exact_svd=True,
        )
        self.params = tiny_params(jax.random.PRNGKey(0))
        self.state, self.blocks = init_slr_state(self.params, self.cfg)

    def test_init_zero_state_and_penalty(self):
        pen = penalty(self.params, self.state, self.blocks)
        # with Z = 0 the penalty is sum rho/2 ||X||^2 > 0
        assert float(pen) > 0
        for blk in self.state.values():
            assert float(jnp.abs(blk.p).max()) == 0
            assert float(jnp.abs(blk.y).max()) == 0

    def test_penalty_grad_is_rho_times_residual(self):
        g = jax.grad(lambda p: penalty(p, self.state, self.blocks))(self.params)
        blk = self.state["embed/embedding"]
        x = self.params["embed"]["embedding"]
        np.testing.assert_allclose(
            g["embed"]["embedding"], blk.rho * x, rtol=1e-5
        )  # Z=0 at init

    def test_update_reduces_reconstruction(self):
        state, stats = admm_update(self.params, self.state, self.blocks, self.cfg, 0)
        err0 = float(stats["_mean_recon_err"])
        for step in range(1, 6):
            state, stats = admm_update(self.params, state, self.blocks, self.cfg, step)
        assert float(stats["_mean_recon_err"]) <= err0 + 1e-6

    def test_surrogate_close_to_x_on_slr_data(self):
        state = self.state
        for step in range(8):
            state, stats = admm_update(self.params, state, self.blocks, self.cfg, step)
        surr = surrogate_params(self.params, state, self.blocks)
        x = self.params["embed"]["embedding"]
        rel = float(jnp.linalg.norm(surr["embed"]["embedding"] - x) / jnp.linalg.norm(x))
        assert rel < 0.12  # ground-truth SLR matrix is recoverable

    def test_surrogate_low_rank_product_at_full_precision(self):
        """A TPU's default precision would round L = P @ Vt through bfloat16;
        the deployed weights ask for HIGHEST whatever the backend."""
        state, _ = admm_update(self.params, self.state, self.blocks, self.cfg, 0)
        hlo = jax.jit(surrogate_params, static_argnums=2).lower(
            self.params, state, self.blocks).as_text()
        dots = [ln for ln in hlo.splitlines() if "dot_general" in ln]
        assert dots and all("precision = [HIGHEST, HIGHEST]" in ln for ln in dots)

    def test_dual_update_identity(self):
        """Y_{k+1} - Y_k == rho (X - L - S) (ADMM dual ascent, Eq. 5)."""
        cfg = SalaadConfig(
            selection=SelectionConfig(min_dim=16), admm_inner_steps=1, exact_svd=True
        )
        state, blocks = init_slr_state(self.params, cfg)
        new_state, _ = admm_update(self.params, state, blocks, cfg, 0)
        blk = new_state["embed/embedding"]
        x = self.params["embed"]["embedding"]
        l = blk.p @ blk.vt
        s = sparse.to_dense(blk.s_coo)
        lhs = blk.y - state["embed/embedding"].y
        np.testing.assert_allclose(lhs, blk.rho * (x - l - s), atol=1e-4)

    def test_stacked_blocks_have_independent_controllers(self):
        # make slice 0 exactly low-rank (no sparse part), slice 1+ mixed
        params = dict(self.params)
        stacked = np.asarray(self.params["layers"]["proj"]).copy()
        u = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (40, 2)))
        v = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (2, 48)))
        stacked[0] = u @ v * 0.01
        params["layers"] = {"proj": jnp.asarray(stacked)}
        state, blocks = init_slr_state(params, self.cfg)
        for step in range(4):
            state, stats = admm_update(params, state, blocks, self.cfg, step)
        alphas = np.asarray(state["layers/proj"].alpha)
        assert alphas.shape == (3,)
        assert not np.allclose(alphas[0], alphas[1])  # diverged per-slice

    def test_determinism_replay(self):
        """ADMM state after k updates is a pure function of (params, step seq):
        fault-tolerant restart replays identically."""
        s1, _ = admm_update(self.params, self.state, self.blocks, self.cfg, 7)
        s2, _ = admm_update(self.params, self.state, self.blocks, self.cfg, 7)
        for k in s1:
            np.testing.assert_array_equal(np.asarray(s1[k].p), np.asarray(s2[k].p))
            np.testing.assert_array_equal(
                np.asarray(s1[k].s_coo.idx), np.asarray(s2[k].s_coo.idx)
            )

    def test_param_count_shrinks_with_thresholds(self):
        state, _ = admm_update(self.params, self.state, self.blocks, self.cfg, 0)
        full = slr_param_count(state, self.blocks)["_total"]
        # push controller hard by running more updates (alpha/beta grow)
        for step in range(1, 12):
            state, _ = admm_update(self.params, state, self.blocks, self.cfg, step)
        later = slr_param_count(state, self.blocks)["_total"]
        assert later <= full


class TestSparse:
    def test_roundtrip_exact_when_under_cap(self):
        x = jnp.zeros((8, 8)).at[2, 3].set(5.0).at[7, 0].set(-1.0)
        coo = sparse.from_dense(x, cap=10)
        np.testing.assert_allclose(sparse.to_dense(coo), x)
        assert int(sparse.nnz(coo)) == 2

    def test_cap_keeps_largest(self):
        x = jnp.array([[1.0, -5.0], [3.0, 0.5]])
        coo = sparse.from_dense(x, cap=2)
        d = sparse.to_dense(coo)
        np.testing.assert_allclose(d, jnp.array([[0.0, -5.0], [3.0, 0.0]]))

    def test_batched(self):
        x = jnp.stack([jnp.eye(4), 2 * jnp.eye(4)])
        coo = sparse.from_dense(x, cap=6)
        d = sparse.to_dense(coo)
        np.testing.assert_allclose(d, x)
        np.testing.assert_array_equal(np.asarray(sparse.nnz(coo)), [4, 4])
