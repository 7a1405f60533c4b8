"""``chunked_selective_scan`` (Mamba-1's recurrence, a decay for every
(channel, state) pair) against the recurrence run step by step: values and
all six gradients at lengths the chunk does not divide, at chunk = 1 and
chunk = length, in bfloat16 operands, and at decays so strong that a
chunk's product underflows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.nn.conf.state_space import chunked_selective_scan

NAMES = ("x", "dt", "A", "B", "C", "D")


def step_by_step(x, dt, a_rate, bm, cm, skip):
    """S_t[c,n] = exp(dt_t[c] A[c,n]) S_{t-1}[c,n] + dt_t[c] B_t[n] x_t[c];
    y_t[c] = sum_n C_t[n] S_t[c,n] + D[c] x_t[c]: one token after another."""
    def step(s, row):
        xt, dtt, bt, ct = row
        s = (jnp.exp(dtt[:, :, None] * a_rate) * s
             + (dtt * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.sum(s * ct[:, None, :], -1) + skip * xt

    s0 = jnp.zeros((x.shape[0], x.shape[2], bm.shape[-1]), jnp.float32)
    _, y = lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                    for a in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


def operands(t=37, c=24, n=16, batch=2, a_scale=1.0, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (batch, t, c)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, t, c))),
            -a_scale * jnp.exp(jax.random.normal(k[2], (c, n))),
            jax.random.normal(k[3], (batch, t, n)),
            jax.random.normal(k[4], (batch, t, n)),
            jax.random.normal(k[5], (c,)))


def scan(args, chunk):
    return chunked_selective_scan(*args[:5], chunk, skip=args[5])


# 37 = 32 + 4 + 1: no chunk below divides it into powers of two
@pytest.mark.parametrize("chunk", [1, 5, 8, 16, 37, 64])
def test_values_follow_the_recurrence(chunk):
    args = operands()
    want = step_by_step(*args)
    got = scan(args, chunk)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


@pytest.fixture(scope="module")
def gradients():
    args = operands()
    weights = jax.random.normal(jax.random.key(9), args[0].shape)

    def of(fn):
        return jax.grad(lambda *a: jnp.sum(fn(a) * weights),
                        argnums=tuple(range(6)))(*args)

    return {"want": of(lambda a: step_by_step(*a)),
            **{chunk: of(lambda a, chunk=chunk: scan(a, chunk))
               for chunk in (1, 8, 37)}}


@pytest.mark.parametrize("chunk", [1, 8, 37])
@pytest.mark.parametrize("leaf", range(6), ids=NAMES)
def test_every_gradient_follows_the_recurrence(gradients, chunk, leaf):
    got, want = gradients[chunk][leaf], gradients["want"][leaf]
    assert got.shape == want.shape
    assert (float(jnp.max(jnp.abs(got - want)))
            < 2e-4 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("what", ["values", "gradients"])
def test_a_decay_that_underflows_inside_a_chunk_stays_exact(what):
    """A = -exp(6) ~ -400 and dt ~ 1: a chunk's product of decays is
    exp(-2000) = 0 in float32. A form that divided running products would
    give 0 / 0; this one gives the recurrence's numbers, and finite
    gradients."""
    x, dt, _, bm, cm, skip = operands(t=24, c=8)
    a_rate = -jnp.exp(jnp.full((8, 16), 6.0))
    args = (x, dt, a_rate, bm, cm, skip)
    # the product of a chunk's eight decays, the largest over the channels
    assert float(jnp.exp(jnp.max(jnp.sum(dt[:, :8] * a_rate[:, 0], 1)))) == 0
    if what == "values":
        got, want = scan(args, 8), step_by_step(*args)
        assert bool(jnp.all(jnp.isfinite(got)))
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5
        return
    got = jax.grad(lambda *a: jnp.sum(scan(a, 8)), argnums=range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(step_by_step(*a)),
                    argnums=range(6))(*args)
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * max(
            float(jnp.max(jnp.abs(w))), 1e-30)


def test_low_precision_operands_and_no_skip():
    x, dt, a_rate, bm, cm, _ = operands()
    low = [a.astype(jnp.bfloat16) for a in (x, bm, cm)]
    got = chunked_selective_scan(low[0], dt, a_rate, low[1], low[2], 8)
    want = step_by_step(low[0].astype(jnp.float32), dt, a_rate,
                        low[1].astype(jnp.float32),
                        low[2].astype(jnp.float32), 0.0)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_the_backward_pass_holds_entry_states_and_one_chunk():
    """The chunk loop's body is rematerialised: between the passes the
    program keeps the chunks' entry states, not every step's state."""
    args = operands(t=64, c=32, batch=1)
    jaxpr = jax.make_jaxpr(jax.vjp(lambda *a: scan(a, 8), *args)[1])(
        jnp.ones((1, 64, 32)))
    sizes = [np.prod(v.aval.shape) for v in jaxpr.jaxpr.invars
             if hasattr(v.aval, "shape")]
    # 8 entry states of 16 x 32 (and the operands), never 64 x 16 x 32
    assert max(sizes) < 64 * 16 * 32


@pytest.mark.parametrize("bad", ["A", "chunk"])
def test_what_it_refuses(bad):
    x, dt, a_rate, bm, cm, _ = operands()
    with pytest.raises(ValueError):
        if bad == "A":
            chunked_selective_scan(x, dt, a_rate.T, bm, cm, 8)
        else:
            chunked_selective_scan(x, dt, a_rate, bm, cm, 0)
