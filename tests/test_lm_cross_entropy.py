"""``chunked_lm_nll`` (losses/lm_cross_entropy.py): the head and the loss a
chunk of tokens at a time, each chunk's logits made once.  Its value and its
two gradients against the plain ``log_softmax`` form, and the products the
differentiated function holds.  Its callers' own tests are in
``test_hybrid_lm.py``, ``test_zaya.py`` and ``test_byte_lm.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_hybrid_remat import equations, remat_primitive
from unicore_tpu.losses.lm_cross_entropy import chunked_lm_nll


def plain_nll(x, w, target, valid):
    """The whole loss in float32 from ``x`` and ``w`` as the head multiplies
    them (``w`` rounded to ``x``'s dtype): every logit alive at once."""
    w = w.astype(x.dtype).astype(jnp.float32)
    logits = jnp.dot(x.astype(jnp.float32), w, precision="highest")
    lp = jax.nn.log_softmax(logits.reshape(target.shape + (-1,)), axis=-1)
    nll = -jnp.take_along_axis(lp, target[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0))


def inputs(T, d, V, heads, dtype):
    ks = jax.random.split(jax.random.key(T + V), 4)
    shape = (T,) if heads == 1 else (T, heads)
    x = jax.random.normal(ks[0], (T, d)).astype(dtype)
    w = 0.3 * jax.random.normal(ks[1], (d, heads * V))
    target = jax.random.randint(ks[2], shape, 0, V)
    valid = jax.random.uniform(ks[3], shape) < 0.8
    if heads > 1:
        valid = valid.at[:, 1].set(False)  # a head no position counts for
    return x, w, target, valid


# rounding: what the chunked form may differ by from float32's, as a share
# of the gradient's largest element (``x``'s gradient is rounded to ``x``'s
# dtype once, as ``jax.grad`` of the plain form in that dtype rounds it)
CASES = {
    "padded_tail": dict(T=50, heads=1, dtype=jnp.float32, rounding=1e-6),
    "three_heads_one_unused": dict(T=48, heads=3, dtype=jnp.float32,
                                   rounding=1e-6),
    "bfloat16_x_float32_kernel": dict(T=50, heads=1, dtype=jnp.bfloat16,
                                      rounding=2.0 ** -8),
    "cotangent_3_loss_used_twice": dict(T=50, heads=1, dtype=jnp.float32,
                                        rounding=1e-6, scale=3.0),
    "bfloat16_cotangent_3": dict(T=50, heads=1, dtype=jnp.bfloat16,
                                 rounding=2.0 ** -8, scale=3.0),
    "float16_scale_2_15": dict(T=50, heads=1, dtype=jnp.float16,
                               rounding=2.0 ** -11, scale=2.0 ** 15),
}


@pytest.mark.parametrize("case", CASES)
def test_chunked_loss_and_gradients_are_the_plain_forms(case):
    c = CASES[case]
    d, V, chunk, scale = 16, 37, 16, c.get("scale", 1.0)
    x, w, target, valid = inputs(c["T"], d, V, c["heads"], c["dtype"])

    def scaled(nll):
        # what ``Trainer.loss_for_grad`` returns: the scaled loss is
        # differentiated, the loss itself is logged beside it
        def f(x, w):
            loss = nll(x, w)
            return loss * scale, loss
        return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))

    (got_scaled, got), (dx, dw) = scaled(
        lambda x, w: chunked_lm_nll(x, w, target, valid, chunk))(x, w)
    (_, want), (dx32, dw32) = scaled(
        lambda x, w: plain_nll(x, w, target, valid))(x.astype(jnp.float32), w)
    rtol = max(c["rounding"], 1e-5)
    np.testing.assert_allclose(got, want, rtol=rtol)
    np.testing.assert_allclose(got_scaled, scale * want, rtol=rtol)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype
    assert dx.shape == x.shape and dw.shape == w.shape
    for a, b in ((dx, dx32), (dw, dw32)):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_allclose(
            np.asarray(a, np.float32), b, rtol=0,
            atol=2 * c["rounding"] * float(jnp.abs(b).max()))
    if c["heads"] > 1:  # the unused head's columns get no gradient
        assert not dw[:, V:2 * V].any() and dw[:, :V].any()
    # a token that does not count gives its hidden state no gradient
    rows = valid if valid.ndim == 1 else valid.any(-1)
    assert not np.asarray(dx, np.float32)[~np.asarray(rows)].any()


def test_float16_gradients_under_the_scale_keep_what_the_scale_protects():
    """Elements of ``x``'s gradient under float16's smallest normal (6e-5)
    come back, scaled, as the same walk in float32 makes them, to float16's
    own rounding: they are not kept unscaled in float16 between the passes
    (which would keep them to 6e-8, a hundredth of such an element)."""
    T, d = 32, 16  # as many columns as hidden units, and a sure prediction
    ks = jax.random.split(jax.random.key(0), 3)
    target = jax.random.randint(ks[0], (T,), 0, d)
    valid = jnp.ones((T,), bool)
    w = 0.5 * jnp.eye(d) + 0.01 * jax.random.normal(ks[1], (d, d))
    w = w.astype(jnp.float16).astype(jnp.float32)
    x = 20.0 * jax.nn.one_hot(target, d) + jax.random.normal(ks[2], (T, d))
    x, scale = x.astype(jnp.float16), 2.0 ** 15
    scaled = jax.grad(
        lambda x: scale * chunked_lm_nll(x, w, target, valid, 16))
    got, want = scaled(x), np.asarray(scaled(x.astype(jnp.float32)))
    small = (np.abs(want) > 0) & (np.abs(want) < scale * 6e-5)
    assert got.dtype == jnp.float16 and small.mean() > 0.5
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[small], want[small], rtol=2.0 ** -10)


def wide_products(jaxpr, V):
    """The ``dot_general``s of ``jaxpr``, at any depth, with a dimension of
    ``V`` among their operands' or their result's, each with the names of
    the primitives it lies inside."""
    return [
        (eqn, inside) for eqn, inside in equations(jaxpr)
        if eqn.primitive.name == "dot_general" and any(
            V in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
    ]


@pytest.mark.parametrize("heads", [1, 3])
def test_each_chunk_makes_its_logits_once(heads):
    """Differentiated: three vocabulary-sized products (the logits and the
    two gradients), all in the one loop over the chunks, and nothing marked
    for a second forward.  Not differentiated: the logits alone."""
    T, d, V, chunk = 64, 8, 40, 16
    x, w, target, valid = inputs(T, d, V, heads, jnp.bfloat16)
    nll = lambda x, w: chunked_lm_nll(x, w, target, valid, chunk)
    wide = heads * V

    plain = jax.make_jaxpr(nll)(x, w).jaxpr
    assert len(wide_products(plain, wide)) == 1

    grad = jax.make_jaxpr(jax.grad(nll, (0, 1)))(x, w).jaxpr
    assert remat_primitive() not in {
        e.primitive.name for e, _ in equations(grad)}
    loops = [e for e, _ in equations(grad) if e.primitive.name == "scan"
             and wide_products(e.params["jaxpr"].jaxpr, wide)]
    assert len(loops) == 1 and loops[0].params["length"] == T // chunk
    grad = wide_products(grad, wide)
    assert len(grad) == 3 and all(inside == ("scan",) for _, inside in grad)
    # the logits are float32 and are what the two gradient products read
    logits = [e for e, _ in grad if e.outvars[0].aval.shape == (chunk, wide)]
    assert len(logits) == 1 and logits[0].outvars[0].aval.dtype == jnp.float32
    assert all(any(v.aval.dtype == jnp.float32 and wide in v.aval.shape
                   for v in e.invars) for e, _ in grad if e is not logits[0])


def test_forward_mode_is_refused():
    x, w, target, valid = inputs(32, 8, 24, 1, jnp.float32)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda x: chunked_lm_nll(x, w, target, valid, 16), (x,), (x,))
