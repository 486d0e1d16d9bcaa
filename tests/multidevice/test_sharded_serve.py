"""Sharded ServeEngine vs the single-device engine: bit-exactness.

The acceptance contract of mesh serving (docs/serving.md#mesh-serving):
for the same request stream, an engine sharded over a host mesh must
produce

  * bit-identical tokens for every request,
  * bit-identical per-request power counters / energies (the accountant
    gathers operand slices before any counter math, so sharding cannot
    perturb a single toggle count),
  * identical slot churn (allocations, assignment, retirement order) --
    continuous batching is host-side control flow and must not notice
    the mesh.

Greedy decoding makes every run deterministic, so equality is asserted
with ``==``, not tolerances. Stochastic co-tenants are exercised too,
asserting the greedy rows stay bit-identical beside them (sampled rows
themselves are allowed to differ: TP re-associates reductions, and
categorical sampling may amplify a ulp into a different token).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.serve import SamplingParams, ServeConfig, ServeEngine

CACHE_LEN = 48
MAX_SLOTS = 4
RNG = np.random.default_rng(11)


def _prompts(n, lo=2, hi=20):
    return [list(map(int, RNG.integers(0, 256, int(RNG.integers(lo, hi)))))
            for _ in range(n)]

PROMPTS = _prompts(6)
BUDGETS = [5, 3, 6, 4, 5, 3]          # staggered so slots churn


@pytest.fixture(scope="module")
def model():
    cfg = SMOKES["qwen1.5-0.5b"].with_(compute_dtype="float32")
    params = lm.init_model(jax.random.key(0), cfg)
    return cfg, params


def _run(model, mesh, *, slots=MAX_SLOTS, power=True, prompts=PROMPTS,
         budgets=BUDGETS, sampling=None):
    cfg, params = model
    eng = ServeEngine(params, cfg,
                      ServeConfig(max_slots=slots, cache_len=CACHE_LEN,
                                  power_monitor=power),
                      mesh=mesh)
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        kw = {} if sampling is None else {"sampling": sampling[i]}
        eng.submit(p, max_new_tokens=b, **kw)
    finished = eng.run()
    return eng, finished


@pytest.fixture(scope="module")
def reference(model):
    """The single-device run every mesh run is compared against."""
    return _run(model, None)


def _assert_same_stats(eng, ref_eng):
    """Every engine count equal, but host syncs: a mesh engine's
    accountant also gathers its replicated operand once per monitored
    site and recording (every decode step, every admission)."""
    got, want = dict(eng.stats), dict(ref_eng.stats)
    extra = got.pop("host_syncs") - want.pop("host_syncs")
    assert got == want
    assert extra == len(eng._power_weights) * (
        eng.stats["decode_steps"] + eng.cache.allocations)


def _mesh(name):
    data, mdl = name.split("x")
    return make_host_mesh(data=int(data), model=int(mdl))


# ------------------------------------------------------- bit-exactness
@pytest.mark.parametrize("mesh_name", ["2x2", "1x8"])
def test_sharded_engine_is_bit_exact(model, reference, mesh_name):
    """Tokens AND power counters identical on 2x2 and 1x8 host meshes."""
    ref_eng, ref_fin = reference
    eng, fin = _run(model, _mesh(mesh_name))
    assert {r.uid: r.generated for r in fin} == \
           {r.uid: r.generated for r in ref_fin}
    assert {r.uid: r.finish_reason for r in fin} == \
           {r.uid: r.finish_reason for r in ref_fin}
    for got, want in zip(sorted(fin, key=lambda r: r.uid),
                         sorted(ref_fin, key=lambda r: r.uid)):
        # full per-design energy dicts, exact equality -- no tolerances
        assert got.power.energy == want.power.energy, got.uid
        assert got.power.zero_fraction == want.power.zero_fraction
        assert got.power.sampled_steps == want.power.sampled_steps
        assert got.power.decode_steps == want.power.decode_steps
    # serve-wide aggregation across the mesh == single-device aggregate
    assert eng.trace_report().aggregate() == \
           ref_eng.trace_report().aggregate()


@pytest.mark.parametrize("mesh_name", ["2x2", "1x8"])
def test_slot_churn_equivalence(model, reference, mesh_name):
    """Continuous batching must not notice the mesh: same admissions,
    same slot assignment, same retirement order, same reuse count."""
    ref_eng, ref_fin = reference
    eng, fin = _run(model, _mesh(mesh_name))
    assert [r.uid for r in fin] == [r.uid for r in ref_fin]
    assert {r.uid: r.slot for r in fin} == \
           {r.uid: r.slot for r in ref_fin}
    assert {r.uid: (r.start_step, r.finish_step) for r in fin} == \
           {r.uid: (r.start_step, r.finish_step) for r in ref_fin}
    assert eng.cache.allocations == ref_eng.cache.allocations
    _assert_same_stats(eng, ref_eng)


def test_greedy_rows_exact_beside_stochastic_cobatch(model):
    """Greedy requests co-batched with temperature/top-k traffic on a
    mesh == the same greedy requests on one device (row independence
    survives sharding; only the stochastic rows may diverge)."""
    sampling = [SamplingParams() if i % 2 == 0 else
                SamplingParams(temperature=1.1, top_k=9)
                for i in range(len(PROMPTS))]
    _, ref_fin = _run(model, None, power=False, sampling=sampling)
    _, fin = _run(model, _mesh("2x2"), power=False, sampling=sampling)
    ref = {r.uid: r.generated for r in ref_fin}
    got = {r.uid: r.generated for r in fin}
    for uid in range(0, len(PROMPTS), 2):          # the greedy rows
        assert got[uid] == ref[uid], uid


# ------------------------------------------------------- paged engine
def _paged_run(model, mesh, *, chunk=0, prefix=False, power=True):
    from repro.serve import PagingConfig
    cfg, params = model
    eng = ServeEngine(params, cfg, ServeConfig(
        cache_len=CACHE_LEN, power_monitor=power,
        paging=PagingConfig(page_size=8, num_pages=64, max_rows=4,
                            prefill_chunk=chunk, prefix_cache=prefix)),
        mesh=mesh)
    for p, b in zip(PROMPTS, BUDGETS):
        eng.submit(p, max_new_tokens=b)
    return eng, eng.run()


@pytest.mark.parametrize("mesh_name", ["2x2", "1x8"])
def test_paged_engine_on_mesh_bit_exact(model, mesh_name):
    """The paged engine composes with mesh sharding (page axis over
    data, features over model) without perturbing a single token or
    toggle count vs the single-device paged run."""
    ref_eng, ref_fin = _paged_run(model, None)
    eng, fin = _paged_run(model, _mesh(mesh_name))
    assert {r.uid: r.generated for r in fin} == \
           {r.uid: r.generated for r in ref_fin}
    for got, want in zip(sorted(fin, key=lambda r: r.uid),
                         sorted(ref_fin, key=lambda r: r.uid)):
        assert got.power.energy == want.power.energy, got.uid
    assert eng.trace_report().aggregate() == \
           ref_eng.trace_report().aggregate()
    _assert_same_stats(eng, ref_eng)


def test_paged_chunked_prefix_on_mesh_token_equal(model):
    """Chunked prefill + shared-prefix reuse on a mesh reproduce the
    single-device paged engine's greedy tokens (the chunk jit runs with
    explicit cache shardings; prefix bookkeeping is host-side)."""
    _, ref_fin = _paged_run(model, None, chunk=8, prefix=True,
                            power=False)
    eng, fin = _paged_run(model, _mesh("2x2"), chunk=8, prefix=True,
                          power=False)
    assert {r.uid: r.generated for r in fin} == \
           {r.uid: r.generated for r in ref_fin}
    assert eng.stats["chunk_calls"] > 0


# ------------------------------------------------- divisibility fallback
def test_awkward_mesh_shapes_still_bit_exact(model, reference):
    """Meshes whose axes divide nothing cleanly (data=5 over 3 slots;
    model=8 over 4 kv heads) fall back to replication where needed and
    stay bit-exact end to end."""
    _, ref_fin = reference
    want = {r.uid: r.generated for r in ref_fin}
    for mesh in (make_host_mesh(data=5, model=1),
                 make_host_mesh(data=3, model=2)):
        _, fin = _run(model, mesh)
        assert {r.uid: r.generated for r in fin} == want, mesh.shape


def test_make_host_mesh_divisibility_fallback():
    # model=3 does not divide 8 devices: the TP width is HONORED (it
    # decides memory/layout) over a 6-device subset, idling two
    assert dict(make_host_mesh(model=3).shape) == {"data": 2, "model": 3}
    assert dict(make_host_mesh(data=2, model=2).shape) == \
           {"data": 2, "model": 2}          # subset mesh: 4 of 8 devices
    assert dict(make_host_mesh(model=8).shape) == {"data": 1, "model": 8}
    # only an unsatisfiable request falls back (model > device count)
    assert dict(make_host_mesh(model=16).shape) == {"data": 1, "model": 8}
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=4, model=4)     # 16 > 8: never silently wrap


# ------------------------------------------------------------- layouts
def test_serve_rules_and_cache_layouts(model):
    """The sharded engine really uses the TP-only serve rules and the
    slot-axis/data, feature/model cache layout (scan + sequence axes
    never sharded)."""
    cfg, params = model
    mesh = _mesh("2x2")
    eng = ServeEngine(params, cfg,
                      ServeConfig(max_slots=MAX_SLOTS,
                                  cache_len=CACHE_LEN),
                      mesh=mesh)
    # serve rules: vocab -> model; embed (FSDP) axis NOT sharded
    assert eng.params["embed"].value.sharding.spec == \
           jax.sharding.PartitionSpec("model", None)
    specs = [s.spec for s in jax.tree.leaves(eng.cache.shardings)]
    assert any("model" in s for s in specs)
    for leaf, sh in zip(jax.tree.leaves(eng.cache.states),
                        jax.tree.leaves(eng.cache.shardings)):
        spec = list(sh.spec) + [None] * (leaf.ndim - len(sh.spec))
        if leaf.ndim >= 4:                  # stacked group leaf [G,B,S,..]
            assert spec[0] is None          # scan axis never sharded
            assert spec[1] == "data"        # slot axis over data
            assert spec[2] is None          # cache sequence axis local
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def test_sharded_decode_cache_is_donated_in_place(model):
    """Steady-state decode must not double-buffer the sharded KV cache:
    the jitted decode donates the cache argument, so the pre-step
    buffers are consumed (deleted), not copied."""
    cfg, params = model
    eng = ServeEngine(params, cfg,
                      ServeConfig(max_slots=2, cache_len=CACHE_LEN),
                      mesh=_mesh("2x2"))
    eng.submit(PROMPTS[0], max_new_tokens=4)
    eng.step()                              # admit + first decode
    before = jax.tree.leaves(eng.cache.states)
    eng.step()
    assert all(leaf.is_deleted() for leaf in before)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(eng.cache.states))
