"""Latent attention and the expert-share MoE layer against the plain
reference (``chipbench/reference/moonlight.py``), on the CPU at a reduced
width with the published expert counts (64 routed, 6 per token, 8 held,
2 shared), on weights the reference makes from a seed.

Tolerances: the program and the reference both run in float32 ("highest"
matmul precision), and differ only in the order of their sums: prefill
logits agree to about 2e-6 against logits of about 2, so 1e-4 holds them.
A routing flip would move a logit by far more.  Decode reads keys and
values from the latent cache, which holds bfloat16 (8 bits of mantissa):
at these sizes that moves the logits by about 0.01 (the same steps with a
float32 cache agree to 2e-6), so decode is held to 0.03; a wrong RoPE
position, softmax scale or cache mask moves them by 0.5 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import moonlight as ref
from repro.configs import get_arch
from repro.models import get_model
from repro.models.moe import held_experts, moe_share, route_topk

SIZES = dict(d=128, heads=4, nope=32, rope=16, vd=32, rank=64, ff=256,
             fe=64, experts=64, held=8, lo=0, top_k=6, shared=2,
             dense_layers=1, layers=3, vocab=512, vocab_rows=512,
             theta=50000.0, eps=1e-5, routed_scale=2.446, bias_scale=0.1)
PREFILL_ATOL = 1e-4
DECODE_ATOL = 3e-2


def _pair(**changes):
    """(the program's config, the reference's dims) of one small share."""
    dm = ref.Dims(**dict(SIZES, **changes))
    cfg = dataclasses.replace(
        get_arch("moonlight-16b-a3b"), n_layers=dm.layers, d_model=dm.d,
        n_heads=dm.heads, n_kv_heads=dm.heads, d_ff=dm.ff, vocab=dm.vocab,
        kv_lora_rank=dm.rank, qk_nope_head_dim=dm.nope,
        qk_rope_head_dim=dm.rope, v_head_dim=dm.vd, d_expert=dm.fe,
        n_experts=dm.experts, n_experts_held=dm.held, expert_lo=dm.lo,
        top_k=dm.top_k, n_shared_experts=dm.shared,
        first_k_dense=dm.dense_layers)
    return cfg, dm


def _tokens(seed, b, t):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0,
                              SIZES["vocab"], jnp.int32)


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
    """Layer 0 dense and 20 MoE layers holding 8 of 64 experts, at the
    published widths: 2,762,180,352 parameters."""
    cfg = dataclasses.replace(get_arch("moonlight-16b-a3b"), n_layers=21,
                              n_experts_held=8)
    assert cfg.param_count() == 2_762_180_352


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 7])
def test_prefill_logits_match_the_reference(seed):
    cfg, dm = _pair()
    w = ref.serve_weights(dm, seed, jnp.float32)
    toks = _tokens(seed, 2, 32)
    with jax.default_matmul_precision("highest"):
        got, cache = get_model(cfg).prefill(w, toks, 36, "bfloat16",
                                            remat=False)
        want = ref.last_logits(dm, w, toks)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               rtol=0, atol=PREFILL_ATOL)
    assert cache["ckv"].shape == (3, 2, 36, dm.rank + dm.rope)
    rows, busiest = (int(v) for v in cache["expert_rows"])
    assert 0 < busiest <= rows <= 2 * 32 * 6 * 2


def test_prefill_then_decode_matches_the_full_forward():
    """Four decode steps through the latent cache after a prefill; each
    step's logits against the reference's full forward of the prefix."""
    cfg, dm = _pair()
    api = get_model(cfg)
    w = ref.serve_weights(dm, 11, jnp.float32)
    t = 24
    toks = _tokens(12, 2, t + 4)
    with jax.default_matmul_precision("highest"):
        _, cache = api.prefill(w, toks[:, :t], t + 4, "bfloat16",
                               remat=False)
        for i in range(4):
            got, cache = api.decode(w, toks[:, t + i:t + i + 1], cache,
                                    jnp.int32(t + i))
            want = ref.last_logits(dm, w, toks[:, :t + i + 1])
            np.testing.assert_allclose(np.asarray(got[:, 0]),
                                       np.asarray(want), rtol=0,
                                       atol=DECODE_ATOL)
    assert set(cache) == {"ckv", "expert_rows"}


def _moe_layer(dm, seed, bias=None):
    m = ref._layer(dm, jax.random.PRNGKey(seed), moe=True)["moe"]
    if bias is not None:
        m["router_bias"] = bias
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, dm.d))
    return m, x


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of 8 shares of one MoE layer, each holding 8 of
    the 64 experts, plus the shared experts counted once, are the uncut
    reference layer (all 64 held)."""
    _, whole = _pair(held=64)
    m, x = _moe_layer(whole, 5)
    xf = x.reshape(-1, whole.d)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(whole, m, x)
        total = ref.swiglu(m["shared"], x)
        rows = 0
        for share in range(8):
            cfg, _ = _pair(lo=8 * share)
            part = dict(m, **{k: m[k][8 * share:8 * share + 8]
                              for k in ("w1", "w3", "w2")})
            top_e, top_w = route_topk(cfg, part, xf)
            out, counts = held_experts(cfg, part, xf, top_e, top_w)
            total = total + out.reshape(x.shape)
            rows += int(counts[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=PREFILL_ATOL)
    assert rows == xf.shape[0] * 6        # every assignment, once


@pytest.mark.parametrize("chosen", [[0], [0, 1, 2, 3, 4, 5]],
                         ids=["one-expert", "six-held"])
def test_no_token_is_dropped(chosen):
    """Every token routed to the same held expert (or all six choices
    held): the share still matches the reference, which drops nothing."""
    cfg, dm = _pair()
    bias = jnp.zeros((dm.experts,)).at[jnp.array(chosen)].set(100.0)
    m, x = _moe_layer(dm, 9, bias)
    with jax.default_matmul_precision("highest"):
        got, counts = moe_share(cfg, m, x)
        want = ref.moe(dm, m, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=PREFILL_ATOL)
    n = x.shape[0] * x.shape[1]
    rows, busiest = (int(c) for c in counts)
    assert busiest == n and n * len(chosen) <= rows <= n * 6
