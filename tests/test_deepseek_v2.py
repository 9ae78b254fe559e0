"""DeepSeek-V2-Lite (latent attention + DeepSeekMoE) at the smoke size on
the CPU: the decode engine and the model path against the plain reference
(``models/reference_deepseek_v2.py``), the latent-cache attention against
the reference's full attention, YaRN against the published formulas,
routing without renormalisation or capacity, and the engine's contract.

Tolerances: the engine and the reference compute the same float32
mathematics in different orders (matvecs in row chunks, ``wkv_b`` absorbed
into the query and output, experts gathered rather than masked), so their
logits differ by float32 rounding, about 1e-6 of the largest logit here;
1e-4 leaves room for that and is far below what one bfloat16 pass per
matvec gives (``tests/bench/test_bench_decode.py``)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pim
from repro.configs import get_config
from repro.configs.deepseek_v2_lite import (FULL, SMOKE, from_hf,
                                            reference_params, to_hf)
from repro.models import mla, moe, transformer
from repro.models import reference_deepseek_v2 as ref
from repro.models.pim_bridge import (extract_decode_weights,
                                     validate_decode_config)
from repro.pim.decode import DecodeEngine, _route

#: relative logit tolerance (module docstring)
LOGIT_TOL = 1e-4


def _ref_last(rp, seqs, k):
    """The reference's logits at the last ``k`` positions of each
    sequence."""
    return ref.forward(to_hf(SMOKE), rp, seqs,
                       [np.arange(len(s) - k, len(s)) for s in seqs])


def _rel(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max()
                 / np.abs(np.asarray(b)).max())


@pytest.fixture(scope="module")
def model():
    params, _ = transformer.init(jax.random.PRNGKey(3), SMOKE)
    return params, reference_params(params, SMOKE)


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, SMOKE.vocab, n) for n in (9, 13, 5)]


def _serve(params, prompts, steps=6):
    """An engine's streams block-prefilled from ``prompts``, then ``steps``
    lockstep decode steps: (engine, logits (B, steps, V), the backend
    compiles of each step after the first)."""
    compiles, counts = [], []

    def listen(event, _duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with pim.session(banks=1, n_chunks=2) as s:
            eng = DecodeEngine(params, SMOKE, session=s)
            eng.prefill(prompts, max_len=32)
            logits = []
            for _ in range(steps):
                compiles.clear()
                eng.step()
                counts.append(len(compiles))
                logits.append(np.asarray(eng.last_logits))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return eng, np.stack(logits, 1), counts[1:]


@pytest.fixture(scope="module")
def served(model):
    """Three streams block-prefilled from prompts of 9, 13 and 5 tokens,
    then 6 lockstep decode steps, logits kept, and the compiles of every
    step after the first."""
    params, _ = model
    return _serve(params, _prompts())


# -- engine vs the plain reference ---------------------------------------------

def test_engine_logits_match_reference_after_prefill(model, served):
    _, rp = model
    eng, logits, _ = served
    want = _ref_last(rp, [s.tokens[:-1] for s in eng.streams],
                     logits.shape[1])
    for b, w in enumerate(want):
        assert _rel(logits[b], w) < LOGIT_TOL, b


def test_greedy_tokens_match_reference_greedy(model, served):
    """At every generated position the reference's argmax over the same
    prefix is the engine's next token: the reference decoding greedily
    from the prompts gives the engine's tokens."""
    _, rp = model
    eng, logits, _ = served
    steps = logits.shape[1]
    want = _ref_last(rp, [s.tokens[:-1] for s in eng.streams], steps)
    for s, w in zip(eng.streams, want):
        assert list(w.argmax(-1)) == s.tokens[-steps:]


def test_generate_token_by_token_matches_prefill(model, served):
    """``generate`` (no block prefill) yields the tokens of prefill + step."""
    params, _ = model
    eng, logits, _ = served
    s0 = eng.streams[0]
    n = len(s0.tokens) - logits.shape[1]
    with pim.session(banks=1, n_chunks=2) as s:
        out = DecodeEngine(params, SMOKE, session=s).generate(
            np.asarray([s0.tokens[:n]]), logits.shape[1])
    assert list(out[0]) == s0.tokens


def test_expert_groups_are_counted(served):
    eng, _, _ = served
    k = SMOKE.moe_top_k
    for st in eng.steps:
        assert st.expert_requests == 3 * (1 + k) * 2
        assert st.experts_s == st.pim_s["experts"] > 0
        assert 0 < st.route_s and 0 < st.attend_s < st.host_s
    # 6 + 2 * experts handles in each MoE layer, 5 in the dense one
    assert len(eng.pins) == 5 + 5 + 2 * SMOKE.moe_experts


def _step_rows(eng):
    """The engine's tagged requests, in submission order, cut into steps
    (a step opens with layer 0's ``q``)."""
    recs = sorted((r for r in eng.session.telemetry.records
                   if "proj" in r.tags), key=lambda r: r.request_id)
    steps = []
    for r in recs:
        if (r.tags["layer"], r.tags["proj"]) == (0, "q"):
            steps.append([])
        steps[-1].append(r.tags)
    return steps


def test_one_request_per_matrix_a_step(served):
    """Each step sends one request per (layer, matrix) it uses: q, kv_a,
    o and the FFN over all three streams, and each routed expert chosen
    that step over the streams that chose it (each stream through k
    distinct experts).  The per-stream counts are those of one request
    per stream and matvec."""
    eng, _, _ = served
    k, B = SMOKE.moe_top_k, len(eng.streams)
    per_stream = 5 + 3 + 2 * (1 + k)       # dense layer, then the MoE one
    steps = _step_rows(eng)
    assert len(steps) == len(eng.steps)
    for st, tags in zip(eng.steps, steps):
        keys = [(t["layer"], t["proj"]) for t in tags]
        assert st.requests == len(keys) == len(set(keys))
        assert st.matvecs == sum(t["streams"] for t in tags) \
            == B * per_stream
        routed = [t for t in tags if t["proj"][0] == "e"]
        ups = {t["proj"][:-3] for t in routed if t["proj"].endswith(".up")}
        downs = {t["proj"][:-5] for t in routed
                 if t["proj"].endswith(".down")}
        assert ups == downs and 0 < len(ups) <= B * k
        assert st.expert_batches == 2 + 2 * len(ups)
        assert sum(t["streams"] for t in routed) == 2 * B * k
        assert all(t["streams"] == B for t in tags if t["proj"][0] != "e")
        assert st.expert_requests == 2 * B + sum(t["streams"]
                                                 for t in routed)


def test_logits_match_per_stream_engines(model, served):
    """Each stream alone in an engine (one request per stream and matvec)
    gives the batched engine's logits to float32 rounding."""
    params, _ = model
    _, logits, _ = served
    for b, p in enumerate(_prompts()):
        _, alone, _ = _serve(params, [p], steps=logits.shape[1])
        assert _rel(logits[b], alone[0]) < 1e-6, b


def test_varying_expert_streams_compile_nothing(served):
    """Routed experts carry 1 to 3 streams from step to step; operands
    padded to the stream count keep every shape, so after the first step
    nothing compiles."""
    eng, _, compiles = served
    counts = {t["streams"] for tags in _step_rows(eng)[1:] for t in tags
              if t["proj"][0] == "e"}
    assert len(counts) > 1, counts
    assert compiles == [0] * (len(eng.steps) - 1)


def test_model_path_matches_reference(model):
    params, rp = model
    toks = np.random.default_rng(1).integers(0, SMOKE.vocab, 12)
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.forward(params, SMOKE,
                                     tokens=jnp.asarray(toks)[None])
        cache = transformer.init_cache(params, SMOKE, 1, 16)
        dec = []
        for t in range(12):
            lt, cache = transformer.decode_step(
                params, SMOKE, jnp.asarray(toks[t:t + 1])[None], cache)
            dec.append(lt[0, 0])
    want = ref.forward(to_hf(SMOKE), rp, [toks])[0]
    assert _rel(got[0], want) < LOGIT_TOL
    assert _rel(jnp.stack(dec), want) < LOGIT_TOL


def test_model_path_trains():
    params, _ = transformer.init(jax.random.PRNGKey(0), SMOKE)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, SMOKE.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    loss = lambda p: transformer.loss_fn(p, SMOKE, batch)[0]  # noqa: E731
    l0, g = jax.value_and_grad(loss)(params)
    p1 = jax.tree.map(lambda a, b: a - 0.1 * b, params, g)
    assert bool(jnp.isfinite(l0)) and float(loss(p1)) < float(l0)


# -- the latent-cache half against the reference's full attention ---------------

def test_latent_cache_attention_matches_full_attention(model):
    params, rp = model
    p = rp["layers"][0]
    S = 11
    h = jax.random.normal(jax.random.PRNGKey(7), (S, SMOKE.d_model))
    with jax.default_matmul_precision("highest"):
        cos, sin = ref.rope_tables(to_hf(SMOKE), np.arange(S))
        want = ref._attention(to_hf(SMOKE), p, h, cos, sin)
        q_nope, q_pe, c_kv, k_pe = mla.latent(
            SMOKE, (h @ p["wq"])[None], (h @ p["wkv_a"])[None],
            p["kv_norm"], jnp.arange(S)[None])
        for t in (0, 5, S - 1):
            o = mla.attend_latent(SMOKE, q_nope[:, :, t], q_pe[:, :, t],
                                  c_kv, k_pe, jnp.asarray([t + 1]),
                                  p["wkv_b"]) @ p["wo"]
            assert _rel(o[0], want[t]) < 1e-5, t
        block = mla.attend_block(SMOKE, q_nope, q_pe, c_kv, k_pe,
                                 p["wkv_b"], q_block=4) @ p["wo"]
    assert _rel(block[0], want) < 1e-5


# -- YaRN against the published formulas ----------------------------------------

def test_yarn_frequencies_and_softmax_scale_by_hand():
    """V2-Lite: rope dim 64, base 1e4, factor 40 over 4096, beta 32 / 1.
    Correction dims floor(10.47) = 10 and ceil(22.51) = 23; below 10 the
    frequencies are extrapolated (base ** (-2i/64)), from 23 on
    interpolated (/ 40), linear in between.  mscale = 0.1 * 0.707 *
    ln 40 + 1 on both sides, so cos/sin are unscaled and the softmax scale
    is 192 ** -0.5 * mscale ** 2."""
    inv = mla.inv_freq(FULL)
    assert inv.shape == (32,)
    for i, want in ((0, 1.0), (9, 1e4 ** (-18 / 64)),
                    (16, 0.01 * 7 / 13 + 0.01 / 40 * 6 / 13),
                    (23, 1e4 ** (-46 / 64) / 40),
                    (31, 1e4 ** (-62 / 64) / 40)):
        assert inv[i] == pytest.approx(want, rel=1e-6), i
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(FULL) == pytest.approx(192 ** -0.5 * m * m)
    assert mla.softmax_scale(FULL) == pytest.approx(0.1147213867929261)
    assert mla.rope_mscale(FULL) == 1.0
    assert ref.softmax_scale(to_hf(FULL)) == pytest.approx(
        mla.softmax_scale(FULL))
    cos, _ = ref.rope_tables(to_hf(FULL), np.asarray([1]))
    np.testing.assert_allclose(np.asarray(cos[0, :32]), np.cos(inv),
                               rtol=1e-6)


def test_rope_permutes_interleaved_pairs_to_half_split():
    """At position 0 the rope is the permutation alone."""
    x = jnp.arange(8.0)
    got = mla.rope_pe(x[None], jnp.zeros((1,)), SMOKE)[0]
    np.testing.assert_array_equal(np.asarray(got), [0, 2, 4, 6, 1, 3, 5, 7])


# -- routing: no renormalisation, no capacity ------------------------------------

def test_moe_gates_are_not_renormalised_and_nothing_drops():
    cfg = dataclasses.replace(SMOKE, d_ff=8)
    p, _ = moe.init(jax.random.PRNGKey(0), cfg)
    # every token's top expert is expert 0 (positive x, a router column of
    # ones): a capacity of 1.25 would keep only some of the 16 tokens
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 16,
                                                          cfg.d_model)))
    p["router"] = p["router"].at[:, 0].add(0.08)
    with jax.default_matmul_precision("highest"):
        y, _ = moe.apply(p, cfg, x)
        h = x[0]
        probs = jax.nn.softmax(h @ p["router"], axis=-1)
        g, idx = jax.lax.top_k(probs, cfg.moe_top_k)
        assert bool((idx == 0).any(-1).all())
        assert float(g.sum(-1).max()) < 0.99          # not renormalised
        want = transformer.swiglu(h, p["shared"]["wi"], p["shared"]["wo"])
        for k in range(cfg.moe_top_k):
            for t in range(16):
                e = int(idx[t, k])
                want = want.at[t].add(g[t, k] * transformer.swiglu(
                    h[t], p["wi"][e], p["wo"][e]))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    eg, eidx = _route(cfg.moe_top_k, h, p["router"])
    np.testing.assert_allclose(np.asarray(eg), np.asarray(g), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(eidx), np.asarray(idx))


def test_renormalised_gates_where_the_model_says_so():
    cfg = dataclasses.replace(SMOKE, moe_norm_topk=True)
    g = moe._norm_gates(cfg, jnp.asarray([[0.3, 0.1]]))
    np.testing.assert_allclose(np.asarray(g), [[0.75, 0.25]])
    assert moe._norm_gates(SMOKE, g) is g
    assert get_config("deepseek-moe-16b").moe_norm_topk is False
    assert get_config("kimi-k2-1t-a32b").moe_norm_topk is True


# -- the engine's contract -----------------------------------------------------

def test_bridge_accepts_v2_lite_and_emits_its_operands(model):
    validate_decode_config(dataclasses.replace(FULL, dtype=jnp.float32))
    validate_decode_config(SMOKE)
    params, _ = model
    layers = extract_decode_weights(params, SMOKE)
    dense, moe_l = layers
    assert set(dense.mats) == {"q", "kv_a", "o", "up", "down"}
    assert dense.mats["q"]["w"].shape == (
        SMOKE.n_heads * (SMOKE.qk_nope_head_dim + SMOKE.qk_rope_head_dim),
        SMOKE.d_model)
    assert dense.mats["kv_a"]["w"].shape == (
        SMOKE.kv_lora_rank + SMOKE.qk_rope_head_dim, SMOKE.d_model)
    assert moe_l.mats["shared.up"]["wg"].shape == (
        SMOKE.d_ff * SMOKE.moe_shared_experts, SMOKE.d_model)
    assert moe_l.mats["e7.down"]["w"].shape == (SMOKE.d_model, SMOKE.d_ff)
    assert len(moe_l.mats) == 5 + 2 * SMOKE.moe_experts
    assert set(moe_l.host) == {"norm1", "norm2", "kv_norm", "wkv_b",
                               "router"}


@pytest.mark.parametrize("arch,match", [
    ("jamba-1.5-large-398b", "mixer 'mamba'"),
    ("xlstm-125m", "mixer"),
    ("stablelm-12b", "parallel_block"),
    ("deepseek-moe-16b", "ffn"),
])
def test_bridge_still_rejects_what_it_rejected(arch, match):
    cfg = get_config(arch, smoke=True)
    if arch.startswith("jamba"):         # its mamba layer, not its MoE
        cfg = dataclasses.replace(cfg, moe_experts=0)
    with pytest.raises(ValueError, match=match):
        validate_decode_config(cfg)


def test_bridge_rejects_v2_lite_in_bfloat16():
    with pytest.raises(ValueError, match="float32"):
        validate_decode_config(dataclasses.replace(SMOKE,
                                                   dtype=jnp.bfloat16))


def test_hf_config_round_trip():
    assert from_hf(to_hf(FULL)) == dataclasses.replace(
        FULL, name="deepseek-v2-27l", dtype=jnp.float32, remat=False)
    assert FULL.total_params() / 1e9 == pytest.approx(15.7, rel=0.01)
    with pytest.raises(ValueError):
        from_hf({**to_hf(FULL), "q_lora_rank": 1536})


def test_norm_eps_reaches_the_model_path():
    """Danube3's published 1e-5 moves its logits off the 1e-6 default."""
    cfg = get_config("h2o-danube-3-4b", smoke=True)
    assert cfg.norm_eps == 1e-5
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.arange(6)[None]
    a, _ = transformer.forward(params, cfg, tokens=toks)
    b, _ = transformer.forward(params, dataclasses.replace(
        cfg, norm_eps=1e-6), tokens=toks)
    assert float(jnp.abs(a - b).max()) > 0
