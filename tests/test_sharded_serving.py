"""Tensor-parallel serving tests (PR 9 tentpole): mesh-aware engines.

The core contract: an engine built with ``EngineConfig(mesh="model=N")``
streams greedy tokens IDENTICAL to the single-device engine — across paged /
chunked-prefill / int8-KV / speculative / elastic-pressure / prefix-cached
configs and both kernel implementations — with allclose logits, zero jit
retraces, payload pools sharded over the head axis, and the BlockAllocator /
prefix cache untouched (block tables stay replicated host bookkeeping).

The parity matrix needs real multi-device placement, so those classes skip
unless the process was launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the env var must be
set before the FIRST jax import — a dedicated CI step provides it; under the
plain tier-1 run conftest imports jax first and these skip). Mesh-spec and
EngineConfig validation tests run everywhere.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_arch
from repro.core.admm import SalaadConfig, admm_update, init_slr_state
from repro.core.selection import SelectionConfig
from repro.models import model as model_lib
from repro.parallel.sharding import ServingMesh, parse_mesh_spec
from repro.serving.elastic import ModelBank
from repro.serving.engine import (
    EngineCapabilityError,
    EngineConfig,
    PagedServingEngine,
    ReferenceEngine,
    _device_put_tiers,
    _kv_pool_device_bytes,
)
from repro.serving.speculative import SpeculativeEngine
from repro.serving.telemetry import engine_provenance

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 set "
           "before the first jax import (see the CI sharded-serving step)",
)

PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [2, 4, 6, 8, 10], [1, 2, 3, 9]]


def drive(engine, tiers=True, max_new=6):
    """Submit the fixed prompt set (alternating tiers) and run to drain."""
    for i, p in enumerate(PROMPTS):
        engine.submit(p, max_new_tokens=max_new,
                      tier=(i % 2) if tiers else None)
    done = engine.run()
    return {tuple(r.prompt): r.out_tokens for r in done}


def ecfg(**kw):
    return EngineConfig(max_slots=4, max_len=32, block_size=8, **kw)


# ------------------------------------------------------- single-device safe --


class TestMeshSpec:
    """parse_mesh_spec + EngineConfig format validation (no devices needed)."""

    def test_defaults_and_forms(self):
        assert parse_mesh_spec("") == {"data": 1, "model": 1}
        assert parse_mesh_spec("model=2") == {"data": 1, "model": 2}
        assert parse_mesh_spec("model=4,data=2") == {"data": 2, "model": 4}
        assert parse_mesh_spec(" data=2 , model=2 ") == {"data": 2, "model": 2}

    @pytest.mark.parametrize("bad", ["tp=2", "model", "model=0", "model=-1",
                                     "model=x", "model:2"])
    def test_bad_specs_name_the_field(self, bad):
        with pytest.raises(ValueError, match="mesh="):
            parse_mesh_spec(bad)

    def test_engine_config_validates_at_construction(self):
        with pytest.raises(ValueError, match="mesh="):
            ecfg(mesh="tp=2")
        with pytest.raises(ValueError, match="mesh="):
            ecfg(mesh=2)  # must be the spec STRING, not an int

    def test_engine_config_mesh_stays_json_safe(self):
        cfg = ecfg(mesh="model=2")
        assert json.loads(json.dumps(dataclasses.asdict(cfg)))["mesh"] == "model=2"

    def test_capabilities_report_tensor_parallel(self):
        for eng in (PagedServingEngine, SpeculativeEngine):
            assert eng.capabilities()["features"]["tensor_parallel"] is True
        assert ReferenceEngine.capabilities()["features"]["tensor_parallel"] \
            is False


# ------------------------------------------------------------ multi-device --


@pytest.fixture(scope="module")
def trained():
    """Widened reduced arch (4 q + 4 kv heads so model=4 divides) with a
    2-tier factored bank — the shared fixture for the whole parity matrix."""
    cfg = dataclasses.replace(
        get_arch("salaad_llama_60m").reduced(), num_heads=4, num_kv_heads=4
    )
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    scfg = SalaadConfig(
        selection=SelectionConfig(min_dim=16), rho_constant=5.0, exact_svd=True
    )
    state, blocks = init_slr_state(params, scfg)
    for step in range(3):
        state, _ = admm_update(params, state, blocks, scfg, step)
    bank = ModelBank.build(cfg, params, state, blocks, budgets=(1.0, 0.5),
                           fmt="factored")
    return cfg, params, state, blocks, bank


# engine-config deltas exercised at every mesh size; each routes through a
# different serving subsystem that must inherit TP unchanged
PARITY_CONFIGS = {
    "paged": {},
    "int8_kv": dict(kv_dtype="int8"),
    "chunked_prefill": dict(prefill_chunk=8),
    "prefix_cache": dict(prefix_cache=True),
    "pressure_tiers": dict(tier_policy="pressure", num_blocks=10),
    "speculative": dict(spec_k=3),
}


@needs8
class TestShardedParity:
    @pytest.mark.parametrize("name", sorted(PARITY_CONFIGS))
    def test_greedy_tokens_identical(self, trained, name):
        *_, bank = trained
        kw = PARITY_CONFIGS[name]
        cls = SpeculativeEngine if name == "speculative" else PagedServingEngine
        tiers = name != "speculative"
        base = drive(cls(bank, ecfg(**kw)), tiers)
        for spec in ("model=2", "model=4"):
            eng = cls(bank, ecfg(mesh=spec, **kw))
            assert drive(eng, tiers) == base, (name, spec)
            assert eng.stats_snapshot()["jit_retraces"] == 0, (name, spec)

    def test_data_axis_parity(self, trained):
        """Batch parallelism: the 'data' axis replicates weights and KV
        pools and shards only the in-flight batch, alone or combined with
        'model' — greedy streams stay identical and nothing retraces."""
        *_, bank = trained
        for kw in ({}, dict(prefix_cache=True)):
            base = drive(PagedServingEngine(bank, ecfg(**kw)))
            for spec in ("data=2", "model=2,data=2"):
                eng = PagedServingEngine(bank, ecfg(mesh=spec, **kw))
                assert drive(eng) == base, (kw, spec)
                assert eng.stats_snapshot()["jit_retraces"] == 0, (kw, spec)

    def test_pallas_kernel_paths(self, trained):
        """kernel_impl='pallas' routes decode through the scalar-prefetch
        paged kernel and chunked prefill through the k-wide variant — both
        shard_map-wrapped over the head axis under a mesh."""
        cfg, params, state, blocks, _ = trained
        pcfg = dataclasses.replace(cfg, kernel_impl="pallas")
        bank = ModelBank.build(pcfg, params, state, blocks, budgets=(1.0,),
                               fmt="factored")
        for kw in ({}, dict(prefill_chunk=8)):
            base = drive(PagedServingEngine(bank, ecfg(**kw)), False)
            for spec in ("model=2", "model=4"):
                eng = PagedServingEngine(bank, ecfg(mesh=spec, **kw))
                assert drive(eng, False) == base, (kw, spec)
                assert eng.stats_snapshot()["jit_retraces"] == 0

    def test_logits_allclose(self, trained):
        """Full-forward oracle: logits under the sharded param placement are
        allclose to single-device (bitwise identity is NOT expected — the
        row-parallel o/down psums reassociate the contraction)."""
        cfg, *_, bank = trained
        tier0 = next(iter(bank)).model
        toks = np.arange(1, 9, dtype=np.int32)[None, :]
        ref = np.asarray(tier0.forward(toks))

        def fwd(p, t):
            return model_lib._forward(p, {"tokens": t}, cfg)[0]

        for spec in ("model=2", "model=4"):
            smesh = ServingMesh.from_spec(spec)
            sparams = _device_put_tiers([tier0.params], smesh)[0]
            with smesh:
                got = np.asarray(jax.jit(fwd)(sparams, toks))
            np.testing.assert_allclose(ref, got, atol=2e-5, rtol=2e-5)


@needs8
class TestShardingInvariants:
    def test_pools_shard_tables_replicate(self, trained):
        *_, bank = trained
        per_dev = {}
        for n in (2, 4):
            eng = PagedServingEngine(bank, ecfg(mesh=f"model={n}"))
            payload_spec = eng.cache.k.sharding.spec
            assert payload_spec == P(None, None, "model", None, None)
            assert eng.cache.block_table.sharding.spec == P()
            drive(eng, tiers=True)  # table commits stay replicated mid-stream
            assert eng.cache.block_table.sharding.spec == P()
            bytes_by_dev = _kv_pool_device_bytes(eng.cache)
            assert len(bytes_by_dev) == n
            assert len(set(bytes_by_dev.values())) == 1  # balanced
            per_dev[n] = next(iter(bytes_by_dev.values()))
        # equal total budget -> per-device residency shrinks with the axis
        assert per_dev[4] * 2 == per_dev[2]

    def test_data_axis_replicates_pools(self, trained):
        """'data' carries the batch only: payload pools stay replicated —
        each data replica holds the FULL pool, so per-device residency is
        2x the model=2 placement (which splits the head axis) and the spec
        carries no mesh axis."""
        *_, bank = trained
        eng = PagedServingEngine(bank, ecfg(mesh="data=2"))
        assert eng.cache.k.sharding.spec == P()
        drive(eng, tiers=True)
        data_bytes = _kv_pool_device_bytes(eng.cache)
        assert len(data_bytes) == 2
        assert len(set(data_bytes.values())) == 1
        model_bytes = _kv_pool_device_bytes(
            PagedServingEngine(bank, ecfg(mesh="model=2")).cache)
        assert next(iter(data_bytes.values())) \
            == 2 * next(iter(model_bytes.values()))

    def test_allocator_and_prefix_cache_unchanged(self, trained):
        """Block accounting and radix-cache hits are pure host bookkeeping:
        identical whether or not the payload pools are sharded."""
        *_, bank = trained
        shared = list(range(1, 17))  # two full pages at block_size=8

        def hits_and_free(mesh):
            eng = PagedServingEngine(bank, ecfg(mesh=mesh, prefix_cache=True))
            for _ in range(2):  # second round re-walks the published prefix
                eng.submit(shared + [21], max_new_tokens=4, tier=0)
                eng.submit(shared + [22], max_new_tokens=4, tier=0)
                eng.run()
            return eng.prefix_hits, eng.allocator.free_blocks

        assert hits_and_free("model=2") == hits_and_free(None)

    def test_provenance_and_gauge(self, trained):
        *_, bank = trained
        eng = PagedServingEngine(bank, ecfg(mesh="model=2,data=2"))
        prov = engine_provenance(eng)
        assert prov["mesh"] == {
            "axis_names": ["data", "model"],
            "shape": {"data": 2, "model": 2},
            "num_devices": 4,
        }
        gauge = prov["telemetry"]["serve_kv_pool_device_bytes"]
        assert len(gauge) == 4 and all(v > 0 for v in gauge.values())
        flat = PagedServingEngine(bank, ecfg())
        assert engine_provenance(flat)["mesh"] is None


@needs8
class TestMeshValidation:
    """Device-dependent EngineConfig/engine checks (format-only validation is
    in TestMeshSpec above)."""

    def test_model_axis_must_divide_heads(self, trained):
        *_, bank = trained  # 4 heads: model=8 cannot split them
        with pytest.raises(ValueError, match="must divide num_heads=4"):
            PagedServingEngine(bank, ecfg(mesh="model=8"))

    def test_mesh_larger_than_device_count(self, trained):
        *_, bank = trained
        with pytest.raises(ValueError, match="exceeds the 8 available"):
            PagedServingEngine(bank, ecfg(mesh="model=4,data=4"))

    def test_bsr_formats_rejected(self, trained):
        cfg, params, state, blocks, _ = trained
        bank = ModelBank.build(cfg, params, state, blocks, budgets=(1.0,),
                               fmt="bsr", bsr_block=32)
        with pytest.raises(ValueError, match="'bsr'"):
            PagedServingEngine(bank, ecfg(mesh="model=2"))
        # unsharded bsr serving is untouched
        assert drive(PagedServingEngine(bank, ecfg()), False)

    def test_reference_engine_rejects_mesh(self, trained):
        *_, bank = trained
        with pytest.raises(EngineCapabilityError, match="mesh="):
            ReferenceEngine(bank, ecfg=EngineConfig(max_slots=1,
                                                    mesh="model=2"))
