"""Kimi Delta Attention and the hybrid (KDA beside NoPE latent attention)
backbone against the plain references: ``kernels/ref.py``'s per-token
recurrence for the chunked form, ``chipbench/reference/kimi_linear.py``
for the model, on the CPU at a reduced width with the published expert
counts (256 routed, 8 per token, 8 held, 1 shared) and layer pattern
(3 KDA : 1 MLA, the first layer dense), on weights the reference makes
from a seed.

Tolerances:
  * chunked against per-token, both float32 at "highest" precision: they
    differ only in the order of their sums, about 1e-7 on outputs of
    about 0.3, so 2e-5 holds them.  A state rounded to bfloat16 after each
    token moves them by 1e-3 or more, a dropped delta term or decay by
    far more (``test_the_chunk_tolerance_catches``).
  * prefill logits, program against reference, both float32: about 2e-6
    on logits of about 2, held to 1e-4 (as ``test_models_mla_moe.py``); a
    routing flip or a wrong layer moves them by far more.
  * decode after prefill: the latent cache and the conv tail hold
    bfloat16 (8 bits of mantissa), which moves logits by about 0.01; held
    to 0.03 (as ``test_models_mla_moe.py``).  The KDA state in the cache
    is float32 and is checked at the layer, where a float32 prefill then
    decode agrees with the whole prompt's prefill to 1e-6 and one rounded
    to bfloat16 does not (``test_layer_decode_continues_the_prefill``).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import kimi_linear as ref
from repro.configs import get_arch
from repro.kernels import ref as kernels
from repro.models import get_model
from repro.models.kda import init_kda, kda_decode, kda_prefill
from repro.models.mla import init_mla, mla_prefill
from repro.models.moe import held_experts, route_topk

KDA_ATOL = 2e-5
PREFILL_ATOL = 1e-4
DECODE_ATOL = 3e-2

KINDS = ("kda", "kda", "kda", "mla")
SIZES = dict(d=128, heads=4, nope=32, rope=16, vd=32, rank=64, ff=256,
             fe=64, experts=256, held=8, lo=0, top_k=8, shared=1,
             dense_layers=1, layers=4, kinds=KINDS, kda_heads=4, kda_dim=32,
             conv=4, gate_rank=32, vocab=512, vocab_rows=512, eps=1e-5,
             routed_scale=2.446, bias_scale=0.1)


def _pair(**changes):
    """(the program's config, the reference's dims) of one small share."""
    dm = ref.Dims(**dict(SIZES, **changes))
    cfg = dataclasses.replace(
        get_arch("kimi-linear-48b-a3b"), n_layers=dm.layers, d_model=dm.d,
        n_heads=dm.heads, n_kv_heads=dm.heads, d_ff=dm.ff, vocab=dm.vocab,
        kv_lora_rank=dm.rank, qk_nope_head_dim=dm.nope,
        qk_rope_head_dim=dm.rope, v_head_dim=dm.vd, d_expert=dm.fe,
        n_experts=dm.experts, n_experts_held=dm.held, expert_lo=dm.lo,
        top_k=dm.top_k, n_shared_experts=dm.shared,
        first_k_dense=dm.dense_layers, kda_heads=dm.kda_heads,
        kda_head_dim=dm.kda_dim, kda_conv=dm.conv,
        kda_gate_rank=dm.gate_rank, kda_chunk=16,
        kda_layers=tuple(i + 1 for i, k in enumerate(dm.kinds)
                         if k == "kda"))
    return cfg, dm


@functools.lru_cache(maxsize=None)
def _weights(dm, seed):
    return jax.jit(ref.serve_weights, static_argnums=(0, 1, 2))(
        dm, seed, jnp.float32)


def _tokens(seed, b, t):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0,
                              SIZES["vocab"], jnp.int32)


# ------------------------------------------------------------ the kernel

def _scan_inputs(seed, t, a_log=0.0, state=0.0, b=2, h=3, dk=16, dv=8):
    """q (L2-normed, scaled), k (L2-normed), v, log decays -exp(a_log)
    softplus(.), b in (0, 1), and a state of scale ``state``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = l2(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jnp.exp(a_log) * jax.nn.softplus(
        jax.random.normal(ks[3], (b, t, h, dk)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = state * jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("t,a_log,state", [
    (16, 0.0, 0.0),          # one whole chunk
    (40, 0.0, 0.0),          # crosses two chunk edges, ends mid-chunk
    (7, 0.0, 0.5),           # shorter than a chunk, from a non-zero state
    (48, 2.0, 0.5),          # three whole chunks
    (37, 6.0, 0.5),          # strong decays: g near -50 a token
], ids=["one-chunk", "crossing", "short-nonzero-state", "three-chunks",
        "strong-decay"])
def test_chunked_matches_the_per_token_recurrence(t, a_log, state):
    args = _scan_inputs(t, t, a_log, state)
    want_o, want_s = jax.jit(kernels.kda_naive)(*args)
    got_o, got_s = jax.jit(functools.partial(kernels.kda_chunked,
                                             chunk=16))(*args)
    assert np.all(np.isfinite(np.asarray(got_o)))
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=0, atol=KDA_ATOL)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=0, atol=KDA_ATOL)


def _bf16_state_naive(q, k, v, g, b, state):
    def step(S, x):
        S, o = kernels.kda_step(S, *x)
        return S.astype(jnp.bfloat16).astype(jnp.float32), o
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def _no_delta_naive(q, k, v, g, b, state):
    return kernels.kda_naive(q, jnp.zeros_like(k), v, g, b, state)


def _no_decay_chunked(q, k, v, g, b, state):
    return kernels.kda_chunked(q, k, v, jnp.zeros_like(g), b, state,
                               chunk=16)


@pytest.mark.parametrize("broken", [_bf16_state_naive, _no_delta_naive,
                                    _no_decay_chunked],
                         ids=["bf16-state", "no-delta", "no-decay"])
def test_the_chunk_tolerance_catches(broken):
    args = _scan_inputs(3, 40, 0.0, 0.5)
    want, _ = kernels.kda_naive(*args)
    got, _ = broken(*args)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * KDA_ATOL


def test_layer_decode_continues_the_prefill():
    """A KDA layer's prefill of 24 tokens then 4 decode steps (state and
    conv tail carried, float32) against its prefill of all 28; the same
    with the state rounded to bfloat16 between steps does not agree."""
    cfg, _ = _pair()
    p = init_kda(cfg, jax.random.PRNGKey(4), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 28, cfg.d_model))
    prefill = jax.jit(functools.partial(kda_prefill, cfg))
    decode = jax.jit(functools.partial(kda_decode, cfg))
    with jax.default_matmul_precision("highest"):
        want, _, _ = prefill(p, x)
        for rounded in (False, True):
            out, state, tail = prefill(p, x[:, :24])
            for i in range(24, 28):
                if rounded:
                    state = state.astype(jnp.bfloat16).astype(jnp.float32)
                o, state, tail = decode(p, x[:, i:i + 1], state, tail)
                gap = float(jnp.max(jnp.abs(o[:, 0] - want[:, i])))
            assert (gap > 1e-4) if rounded else (gap < 1e-5), gap


# ------------------------------------------------------------- the model

def test_reference_weights_have_the_programs_tree():
    cfg, dm = _pair()
    program = jax.eval_shape(lambda: get_model(cfg).init(
        jax.random.PRNGKey(0), jnp.bfloat16))
    ours = jax.eval_shape(lambda: ref.serve_weights(dm, 0))
    assert jax.tree.structure(program) == jax.tree.structure(ours)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(program)] == \
        [(x.shape, x.dtype) for x in jax.tree.leaves(ours)]
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(ours))


def test_param_count_of_the_benchmarked_share():
    """Published layers 1-20 (the dense KDA layer, 14 KDA and 5 latent
    MoE layers) holding 8 of 256 experts, at published widths."""
    cfg = dataclasses.replace(get_arch("kimi-linear-48b-a3b"), n_layers=20,
                              n_experts_held=8)
    assert cfg.layer_kinds().count("kda") == 15
    assert cfg.param_count() == 2_778_650_464


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 7])
def test_prefill_logits_match_the_reference(seed):
    cfg, dm = _pair()
    w = _weights(dm, seed)
    toks = _tokens(seed, 2, 40)
    api = get_model(cfg)
    with jax.default_matmul_precision("highest"):
        got, cache = jax.jit(lambda w, t: api.prefill(w, t, 44, "bfloat16",
                                                      False))(w, toks)
        want = ref.last_logits(dm, w, toks)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               rtol=0, atol=PREFILL_ATOL)
    assert cache["kda_state"].shape == (3, 2, 4, 32, 32)
    assert cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (3, 2, 3, 3 * 4 * 32)
    assert cache["ckv"].shape == (1, 2, 44, dm.rank + dm.rope)
    rows, busiest = (int(v) for v in cache["expert_rows"])
    assert 0 < busiest <= rows <= 2 * 40 * 8 * 3


def test_prefill_then_decode_matches_the_full_forward():
    """Four decode steps through both kinds of cache (the KDA state and
    conv tail, the latent cache) after a prefill that crosses a chunk
    edge; each step's logits against the reference's full forward of the
    prefix."""
    cfg, dm = _pair()
    api = get_model(cfg)
    w = _weights(dm, 11)
    t = 24
    toks = _tokens(12, 2, t + 4)
    decode = jax.jit(api.decode)
    with jax.default_matmul_precision("highest"):
        _, cache = jax.jit(lambda w, x: api.prefill(
            w, x, t + 4, "bfloat16", False))(w, toks[:, :t])
        for i in range(4):
            got, cache = decode(w, toks[:, t + i:t + i + 1], cache,
                                jnp.int32(t + i))
            want = ref.last_logits(dm, w, toks[:, :t + i + 1])
            np.testing.assert_allclose(np.asarray(got[:, 0]),
                                       np.asarray(want), rtol=0,
                                       atol=DECODE_ATOL)
    assert set(cache) == {"kda_state", "kda_conv", "ckv", "expert_rows"}


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of 32 shares of one MoE layer, each holding 8 of
    the 256 experts, plus the shared expert counted once, are the uncut
    reference layer (all 256 held)."""
    _, whole = _pair(held=256)
    m = jax.jit(ref._layer, static_argnums=(0, 2, 3))(
        whole, jax.random.PRNGKey(5), "mla", True)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, whole.d))
    xf = x.reshape(-1, whole.d)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(whole, m, x)
        total = ref.swiglu(m["shared"], x)
        rows = 0
        for share in range(32):
            cfg, _ = _pair(lo=8 * share)
            part = dict(m, **{k: m[k][8 * share:8 * share + 8]
                              for k in ("w1", "w3", "w2")})
            top_e, top_w = route_topk(cfg, part, xf)
            out, counts = held_experts(cfg, part, xf, top_e, top_w)
            total = total + out.reshape(x.shape)
            rows += int(counts[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=PREFILL_ATOL)
    assert rows == xf.shape[0] * 8        # every assignment, once


def test_nope_latent_attention_ignores_position():
    """Latent attention without RoPE: the same inputs at positions shifted
    by any amount give the same output and cache entry."""
    cfg, _ = _pair()
    p = init_mla(cfg, jax.random.PRNGKey(8), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 12, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    a, ea = mla_prefill(cfg, p, x, pos)
    b, eb = mla_prefill(cfg, p, x, pos + jnp.array([[5], [1000]]))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ea), np.asarray(eb))
    # with RoPE the same shift does move it
    c, _ = mla_prefill(dataclasses.replace(cfg, mla_nope=False), p, x,
                       pos + 5)
    assert float(jnp.max(jnp.abs(c - a))) > 1e-3


# ------------------------------------------------------------ the server

def test_server_refuses_mixed_lengths_for_a_recurrent_model():
    """Left pads would enter a KDA layer's state: a wave of mixed prompt
    lengths is refused; a wave of one length is served."""
    from repro.serve.server import BatchServer, Request
    cfg = dataclasses.replace(get_arch("kimi-linear-48b-a3b").reduced(),
                              n_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0), jnp.bfloat16)
    srv = BatchServer(cfg, params, batch=2, smax=16)
    with pytest.raises(ValueError, match="recurrent"):
        srv.serve([Request(rid=0, prompt=[1, 2, 3], max_new=1),
                   Request(rid=1, prompt=[4, 5], max_new=1)])
    done = srv.serve([Request(rid=0, prompt=[1, 2, 3], max_new=1),
                      Request(rid=1, prompt=[4, 5, 6], max_new=1),
                      Request(rid=2, prompt=[7, 8], max_new=1)])
    assert sorted(len(r.out) for r in done) == [1, 1, 1]
