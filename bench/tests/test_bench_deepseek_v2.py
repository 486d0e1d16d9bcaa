"""The DeepSeek-V2 family at small sizes on the CPU: the program served
through the slot engine against the float32 reference, this chip's
share of the experts against the uncut layer, the family's work counts
at the configuration file's sizes, the scope readers, and the cell run
whole through the harness."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.harness import cell, spec, system, trace, weights  # noqa: E402

CELL = "dsv2lite.open.plain"
CONFIG = os.path.join(spec.BENCH_DIR, "configs", "deepseek-v2-lite-ep8.json")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "qwen_offline_monitored_3s.xplane.pb.gz")
#: 16 engine steps of this cell's traced run on the chip (seed 2147800201)
DS_DATA = os.path.join(os.path.dirname(DATA),
                       "dsv2lite_open_plain_16steps.xplane.pb.gz")
SEED = 2 ** 31 + 5

#: small widths that keep the configuration's structure (the dense
#: cells' ``_tiny`` sizes are theirs): a dense layer 0 and two expert
#: layers, 2 of 8 experts held (the router over all 8, top 3), one shared
#: expert, YaRN over a 64-position original horizon
TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=32, n_routed_experts=2,
            first_held_expert=2, num_experts_per_tok=3, n_shared_experts=1,
            vocab_size=256, attn_block_k=32,
            published={"num_hidden_layers": 27, "n_routed_experts": 8,
                       "vocab_size": 102400})


def _shrink(cfg: dict) -> dict:
    cfg.update(TINY)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=64)
    return cfg


def _cfg(**kw):
    return dict(_shrink(spec.load_json(CONFIG)), **kw)


def _run_tiny_cell(root: str) -> dict:
    """The cell through the whole harness, cut to CPU size."""
    c = spec.resolve(spec.load_benchmark(), CELL)
    _shrink(c.config)["engine"].update(max_slots=4, cache_len=256)
    t = c.traffic
    t.update(rate=4.0, block=8, warm_seconds=0.5)
    t["prompt"].update(min=16, max=160, median=48, quantum=16)
    t["output"].update(min=8, max=32, median=16, quantum=8)
    return cell.run(c, 2 ** 31 + 77, 1.5, False, time.perf_counter(),
                    require_tpu=False, root=root)


def _family(cfg):
    return spec.load_family(cfg["family"])


def _skewed(params, by: float = 30.0):
    """The router weights scaled up, so that routing is uneven enough for
    capacity buffers to overflow."""
    g = params["stack"]["groups"]["b0"]["ffn"]["router"]
    g.value = g.value * by
    return params


def _served_logits(eng, prompt, forced):
    """Logits the slot engine's own jitted prefill and decode steps give
    for ``prompt`` and then each token of ``forced`` fed through the
    cache: ``[len(forced) + 1, V]``."""
    slot = eng.cache.allocate()
    n = len(prompt)
    toks = np.zeros((1, max(eng._bucket(n), n)), np.int32)
    toks[0, :n] = prompt
    logits, states1 = eng._prefill(eng.params, {"tokens": jnp.asarray(toks)},
                                   np.int32(n))
    out = [np.asarray(logits[0])]
    eng.cache.write_prefill(slot, states1, int(forced[0]), n)
    for i, tok in enumerate(forced):
        logits, eng.cache.states = eng._decode(
            eng.params, eng.cache.states, eng.cache.decode_inputs())
        out.append(np.asarray(logits[slot]))
        eng.cache.advance(slot, int(forced[i + 1]) if i + 1 < len(forced)
                          else 0)
    return np.stack(out)


@pytest.mark.parametrize("held", ["share", "uncut"])
def test_served_logits_match_reference_where_capacity_would_drop(held):
    """A 192-token prompt prefilled and 8 tokens decoded through the
    latent cache by the slot engine's own steps, in float32, give the
    reference's logits at every position, with 2 of the 8 experts held
    (``share``) or all 8 (``uncut``). Uncut, the training path's capacity
    routing on the same prompt does not: it drops tokens."""
    from repro.models import lm
    from repro.serve import ServeConfig, ServeEngine
    cfg = (_cfg() if held == "share"
           else _cfg(n_routed_experts=8, first_held_expert=0))
    fam, ref = _family(cfg)
    arch = fam.arch_config(cfg).with_(compute_dtype="float32")
    params = _skewed(weights.for_config(system.init_fn(arch), SEED, cfg))
    rng = np.random.default_rng(1)
    seq = rng.integers(0, cfg["vocab_size"], 200).astype(np.int32)
    prompt, forced = seq[:192], seq[192:]
    eng = ServeEngine(params, arch, ServeConfig(max_slots=4, cache_len=256))
    got = _served_logits(eng, prompt, forced)
    want = np.asarray(ref.forward(fam.plain_weights(params, cfg), cfg,
                                  jnp.asarray(seq)))[191:]
    # float32 on both sides; what differs is the order of summation (the
    # absorbed latent products, grouped expert matmuls, chunked softmax):
    # a few parts in 1e5 of the largest logit
    tol = 5e-5 * float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    if held == "uncut":
        with jax.default_matmul_precision("highest"):
            h, _, _ = lm.apply_model(params, arch,
                                     {"tokens": jnp.asarray(seq[None])})
            train = np.asarray(lm.logits_fn(params, arch, h)[0, 191:])
        assert np.abs(train - want).max() > 100 * tol


def test_held_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of a 16-expert layer (2 experts each, the
    router over all 16, top 3): their routed parts, with the shared
    experts counted once, add up to the reference's uncut layer."""
    from repro.models import moe as M
    from repro.models.layers import Param
    cfg = _cfg(n_routed_experts=16, first_held_expert=0,
               published={"n_routed_experts": 16})
    _, ref = _family(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ks = jax.random.split(jax.random.key(3), 8)
    full = {"router": 0.3 * jax.random.normal(ks[0], (d, 16)),
            "w_gate": 0.1 * jax.random.normal(ks[1], (16, d, f)),
            "w_up": 0.1 * jax.random.normal(ks[2], (16, d, f)),
            "w_down": 0.1 * jax.random.normal(ks[3], (16, f, d)),
            "s_gate": 0.1 * jax.random.normal(ks[4], (d, f)),
            "s_up": 0.1 * jax.random.normal(ks[5], (d, f)),
            "s_down": 0.1 * jax.random.normal(ks[6], (f, d))}
    x = jax.random.normal(ks[7], (1, 40, d))
    shared = {"up": Param(full["s_up"], ()), "gate": Param(full["s_gate"], ()),
              "down": Param(full["s_down"], ())}
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for chip in range(8):
            mcfg = M.MoEConfig(num_experts=16, top_k=3, expert_ff=f,
                               num_shared=1, shared_ff=f,
                               first_expert=2 * chip, held=2)
            part = {"router": Param(full["router"], ()), "shared": shared}
            for name in ("w_gate", "w_up", "w_down"):
                part[name] = Param(full[name][2 * chip:2 * chip + 2], ())
            y, _ = M.apply_moe(part, x, mcfg, dropless=True)
            total = total + y
        shared_out = M.L.apply_mlp(shared, x)
        total = total - 7 * shared_out
    want = ref._experts(full, x[0], ref._hparams(cfg), None)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_counts_at_the_file_sizes():
    """1338M parameters held here (layer 0 81.0M; each expert layer
    100.4M: MLA 13.8M, shared experts 17.3M, router 0.13M, 8 experts
    69.2M; embedding and head 52.4M) and 13 * (512 + 64) * 2 bytes of
    latent cache a position."""
    cfg = spec.load_json(CONFIG)
    fam, _ = _family(cfg)
    mla = (2048 * 16 * 192 + 2048 * 512 + 2048 * 64 + 2 * 512 * 16 * 128
           + 2048 * 2048)
    assert mla == 13_762_560
    expert = 3 * 2048 * 1408
    layer0 = mla + 512 + 2 * 2048 + 3 * 2048 * 10944
    moe = mla + 512 + 2 * 2048 + 2 * expert + 2048 * 64 + 8 * expert
    assert fam.dense_layer_params(cfg) == layer0
    assert fam.moe_layer_params(cfg) == moe
    assert round(layer0 / 1e6, 1) == 81.0 and round(moe / 1e6, 1) == 100.4
    n = fam.n_params(cfg)
    assert n == layer0 + 12 * moe + 2 * 12800 * 2048 + 2048
    assert round(n / 1e6) == 1338
    assert fam.kv_bytes_per_position(cfg) == 13 * 1152 == 14976
    assert fam.routed_share(cfg) == 0.75
    # a token's matrices: layer 0's, and per expert layer MLA, router,
    # 2 shared and 0.75 routed experts
    per_tok = mla + 3 * 2048 * 10944 + 12 * (
        mla + 2048 * 64 + 2.75 * expert)
    assert fam.token_flops(cfg, 0, False) == pytest.approx(2 * per_tok)
    assert fam.decode_step_bytes(cfg, 1000, 64) == pytest.approx(
        2 * n + 14976 * 1064)
    assert fam.expert_step_bytes(cfg, 64) == pytest.approx(
        12 * (2 * (2048 * 64 + 10 * expert) + 4 * 2048 * 64))
    assert fam.latent_step_bytes(cfg, 1000, 64) == pytest.approx(
        13 * 2 * mla + 14976 * 1064)


def test_program_names_the_scopes_the_readers_read():
    """The decode program's operations carry the ``moe`` scopes (router,
    experts, shared) and the ``mla`` scope with the latent cache read
    (``mla/latent``) in their metadata, which the device trace reports
    as each operation's ``tf_op``."""
    import functools
    import re
    from repro.models import lm
    from repro.models import matmul as mm
    fam, _ = _family(_cfg())
    arch = fam.arch_config(_cfg())
    params = lm.init_model(jax.random.key(0), arch)
    states = lm.make_decode_state(arch, 4, 64)
    col = jnp.zeros((4, 1), jnp.int32)
    step = jax.jit(functools.partial(mm.with_backend, "ref",
                                     lm.make_decode_step(arch)))
    hlo = step.lower(params, states, {"tokens": col, "positions": col}
                     ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("moe/router", "moe/experts", "moe/shared", "mla/latent"):
        assert any(f"/{scope}/" in n for n in names), scope


def test_scope_reader_on_a_recorded_trace():
    """The scope reader re-reads a chip trace: the very window and op
    times of the harness's reduction, the decode program's ``attn`` scope
    inside its device time, the casts XLA hoisted out of the layer scan
    (no name stack) in no scope, and the new metrics absent (None) from a
    program that has no ``moe`` or ``mla`` scope."""
    import types
    from bench.metrics import _scopes
    tr = trace.load(DATA)
    t0, t1 = trace.window(tr)
    ops, modules, window = _scopes.read_file(DATA)
    assert window == (t0, t1)
    assert len(ops) == len(tr.ops)
    attn = _scopes.scope_ns(ops, modules, t0, t1, "attn")
    runs, dec = trace.program_time(tr, t0, t1)["jit__unknown"]
    assert 0 < attn < dec
    # the FFN weights' casts, hoisted out of qwen's layer scan
    decode = [(s, s + d) for n, s, d in modules if n == "jit__unknown"]
    hoisted = {op for op in ops
               if op[4] in ("%convert.35", "%convert.36", "%convert.37")
               and t0 <= op[1] <= t1
               and any(a <= op[1] <= b for a, b in decode)}
    assert len(hoisted) == 3 * runs
    for scope in ("attn", "mlp"):
        taken = set(_scopes.scoped_ops(ops, modules, t0, t1, scope))
        assert taken and all(op[0] for op in taken)
        assert not taken & hoisted
    assert _scopes.scope_ns(ops, modules, t0, t1, "moe") == 0
    cfg = spec.load_json(CONFIG)
    ctx = types.SimpleNamespace(trace=tr, t0=t0, t1=t1, config=cfg,
                                counts=_family(cfg)[0],
                                peaks={"hbm_bytes_per_s": 819e9},
                                window={"decode_log": [(32, 4000)]},
                                scoped_trace=(ops, modules))
    for name in ("expert_ms_per_step", "expert_roofline",
                 "latent_attn_ms_per_step", "latent_attn_roofline"):
        assert spec.load_metric(name).read(ctx) is None, name


def test_scopes_taken_on_a_recorded_deepseek_trace():
    """On a chip trace of this cell, each scope takes the decode
    program's operations pinned here: the grouped expert matmuls (XLA's
    ``ragged-dot`` custom calls, which carry no name stack of their own)
    go to ``moe/experts`` after the operations that consume them; the
    expert weights' float32 -> bfloat16 casts XLA hoists out of the
    layer scan go to no scope; and the readers give these numbers."""
    import types
    from bench.metrics import _scopes
    tr = trace.load(DS_DATA)
    t0, t1 = trace.window(tr)
    ops, modules, window = _scopes.read_file(DS_DATA)
    assert window == (t0, t1)
    runs, _ = trace.program_time(tr, t0, t1)["jit__unknown"]
    assert runs == 16

    def taken(scope):
        return {op[4]: op[0] for op in
                _scopes.scoped_ops(ops, modules, t0, t1, scope)}
    sizes = {scope: len(taken(scope)) for scope in
             ("moe", "router", "experts", "shared", "mla", "latent")}
    assert sizes == {"moe": 32, "router": 3, "experts": 26, "shared": 2,
                     "mla": 97, "latent": 7}
    experts = taken("experts")
    for name in ("%ragged-dot-none", "%ragged-dot-none.1",
                 "%ragged-dot-none.2"):
        assert "/b0/moe/experts/" in experts[name]
    casts = {"%convert.65", "%convert.66", "%convert.67"}
    decode = [(s, s + d) for n, s, d in modules if n == "jit__unknown"]
    hoisted = [op for op in ops if op[4] in casts and t0 <= op[1] <= t1
               and any(a <= op[1] <= b for a, b in decode)]
    # three a decode run (a run cut by the window's edge may miss them)
    assert len(hoisted) >= 3 * (runs - 1)
    assert not any(op[0] for op in hoisted)
    assert not casts & {n for scope in ("moe", "mla", "mlp")
                        for n in taken(scope)}
    cfg = spec.load_json(CONFIG)
    ctx = types.SimpleNamespace(trace=tr, t0=t0, t1=t1, config=cfg,
                                counts=_family(cfg)[0],
                                peaks={"hbm_bytes_per_s": 819e9},
                                window={"decode_log": [(64, 60000)],
                                        "prompt_tokens": 3000},
                                scoped_trace=(ops, modules))
    read = {name: spec.load_metric(name).read(ctx) for name in
            ("expert_ms_per_step", "latent_attn_ms_per_step",
             "expert_roofline", "latent_attn_roofline",
             "prefill_ms_per_ktok")}
    assert read["expert_ms_per_step"] == pytest.approx(6.72720, rel=1e-5)
    assert read["latent_attn_ms_per_step"] == pytest.approx(12.52796,
                                                            rel=1e-5)
    assert 0 < read["expert_roofline"] < 100
    assert 0 < read["latent_attn_roofline"] < 100
    assert read["prefill_ms_per_ktok"] > 0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("checkout"))


def test_cell_runs_correct_through_the_harness(root):
    r = _run_tiny_cell(root)
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == {"logit_gap", "logit_gap_mean"}
    assert all(c["value"] is not None for c in r["checks"].values())
    assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert r["failed"] == 0


def test_altered_token_is_caught(root, monkeypatch):
    from repro.serve import sampling
    orig = sampling.sample_tokens

    def altered(key, logits, temperature, top_k):
        return (orig(key, logits, temperature, top_k) + 1) % logits.shape[-1]
    monkeypatch.setattr(sampling, "sample_tokens", altered)
    r = _run_tiny_cell(root)
    assert not r["correct"]
