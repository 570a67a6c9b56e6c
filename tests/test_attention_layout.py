"""Where a Mosaic kernel takes q, k, v, the attention projections write and
read its ``(B, H, L, D)`` layout inside their own products
(``QuantDense.heads_fused``, chosen by ``_kernel_pins_layout``): same
parameters, same numbers as the flat formulation they replace, which stays
where nothing pins a layout (``return_attn``, a backend without the kernel).

The flat formulation — ``x @ W + b``, split, ``reshape``, ``transpose``,
and back — is kept here as the reference, around the module's own attention
core, so the only thing compared is the form of the projections.  The
compile rehearsal that shows the layout copies gone on a described v5e is
in ``tests/test_tpu_compile.py`` (one file loads libtpu).
"""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import unicore_tpu.modules.multihead_attention as mha
from unicore_tpu.modules import CrossMultiheadAttention, SelfMultiheadAttention
from unicore_tpu.ops import _pallas
from unicore_tpu.quant import dense

# L on the kernels' 128 tile: conftest's interpret mode runs the Pallas path
B, L = 2, 128
SHAPES = {"h12_d64": (12, 64), "h64_d8": (64, 8)}
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _flat_dense(p, x):
    """``nn.Dense`` as the parent ran it: the product rounded to the
    compute dtype, then ``+ bias``."""
    y = x @ p["kernel"].astype(x.dtype)
    return y + p["bias"].astype(x.dtype) if "bias" in p else y


def _split_heads(x, h):
    b, l, e = x.shape
    return x.reshape(b, l, h, e // h).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, l, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, l, h * d)


def _flat_self_attention(params, x, h, key_padding_mask=None, attn_bias=None,
                         return_attn=False, return_kv=False, cache=None):
    """The parent's ``SelfMultiheadAttention.__call__``: flat projections
    around the same ``_attend`` / ``_decode`` core."""
    p = params["params"]
    q, k, v = jnp.split(_flat_dense(p["in_proj"], x), 3, axis=-1)
    q = _split_heads(q, h) * (q.shape[-1] // h) ** -0.5
    k, v = _split_heads(k, h), _split_heads(v, h)
    if cache is not None:
        o, rows = SelfMultiheadAttention._decode(
            None, q, k, v, cache[0], cache[1], None, attn_bias)
        return _flat_dense(p["out_proj"], _merge_heads(o)), rows
    o, weights, probs = mha._attend(
        None, q, k, v, key_padding_mask, attn_bias, 0.0, True, return_attn,
        True)
    o = _flat_dense(p["out_proj"], _merge_heads(o))
    if return_kv:
        return o, (k, v)
    return (o, weights, probs) if return_attn else o


def _setup(shape, bias, dtype):
    h, d = SHAPES[shape]
    e = h * d
    m = SelfMultiheadAttention(e, h, dropout=0.0, bias=bias)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(h), 3)
    x = jax.random.normal(k0, (B, L, e), dtype)
    params = m.init(k1, x)
    if bias:  # zeros at init: give the bias adds something to add
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: 0.1 * jax.random.normal(k2, a.shape, a.dtype)
            if path[-1].key == "bias" else a, params)
    return m, h, params, x


def _absmax(a):
    return float(jnp.abs(jnp.asarray(a, jnp.float32)).max())


def _assert_close(got, want, dtype, what, floor=1e-6):
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    gap = _absmax(got - want) / max(_absmax(want), floor)
    assert gap <= TOL[dtype], f"{what}: relative gap {gap:.3e}"


def _assert_trees_close(got, want, dtype, what):
    """Leaf by leaf, each against its own largest entry — but no smaller a
    yardstick than 1% of the tree's: a key-side bias gradient is zero by
    the softmax's shift invariance, and what is left of it is rounding."""
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    floor = 1e-2 * max(_absmax(w) for w in jax.tree_util.tree_leaves(want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        _assert_close(g, w, dtype, f"{what}{jax.tree_util.keystr(path)}",
                      floor)


def _scalar(out):
    """A loss that reaches every array a call returns."""
    return sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
               for a in jax.tree_util.tree_leaves(out))


@pytest.mark.parametrize("mode", ["plain", "key_padding_mask", "return_attn",
                                  "return_kv"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_head_major_projections_match_the_flat_ones(shape, bias, dtype, mode):
    m, h, params, x = _setup(shape, bias, dtype)
    kw = {}
    if mode == "key_padding_mask":
        kw["key_padding_mask"] = jnp.arange(L)[None, :] >= jnp.array(
            [[L], [L - 37]])
    if mode == "return_attn":
        kw["return_attn"] = True
        kw["attn_bias"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, h, L, L), dtype)
    if mode == "return_kv":
        kw["return_kv"] = True

    def new(params, x):
        out = m.apply(params, x, train=True, **kw)
        if mode == "return_attn":
            out = out[:2]  # probs are softmax(weights): one check is enough
        return _scalar(out), out

    def old(params, x):
        out = _flat_self_attention(params, x, h, **kw)
        if mode == "return_attn":
            out = out[:2]
        return _scalar(out), out

    (_, out_new), grads_new = jax.value_and_grad(
        new, argnums=(0, 1), has_aux=True)(params, x)
    (_, out_old), grads_old = jax.value_and_grad(
        old, argnums=(0, 1), has_aux=True)(params, x)
    _assert_trees_close(out_new, out_old, dtype, "output")
    _assert_trees_close(grads_new, grads_old, dtype, "gradient")


@pytest.mark.parametrize("route, fused", [
    ("kernel", True), ("kernel_with_bias", True), ("return_attn", False),
    ("no_kernel_backend", False), ("decode", False),
    ("materialized_bias", False), ("dropout_off_tpu", False),
    ("ring_requested", False),
])
def test_the_products_make_the_heads_only_where_a_kernel_pins_the_layout(
        route, fused, monkeypatch):
    """The choice is made from what the call can see, by the one decision
    ``_attend`` routes by (``_flash_route``) — no option selects it.  Fused
    exactly when the Mosaic kernel ran."""
    m, h, params, x = _setup("h12_d64", True, jnp.float32)
    if route == "dropout_off_tpu":
        m = m.clone(dropout=0.1)
    if route == "ring_requested":  # no live seq axis: the kernel still runs
        m = m.clone(use_ring=True)
    ran = []
    monkeypatch.setattr(
        mha, "_flash_data_parallel",
        lambda *a, _real=mha._flash_data_parallel, **kw:
        ran.append(True) or _real(*a, **kw))
    made = []
    for name in ("_heads_out_product", "_heads_in_product"):
        monkeypatch.setattr(
            dense, name,
            lambda *a, _real=getattr(dense, name), _name=name:
            made.append(_name) or _real(*a))
    kw = {}
    if route == "return_attn":
        kw["return_attn"] = True
    if route == "no_kernel_backend":
        _pallas.set_interpret(False)  # conftest puts the override back
    if route == "decode":
        x = x[:, :1]
        kw["cache_kv"] = tuple(jnp.zeros((2, B, h, L, 64)))
        kw["cache_positions"] = jnp.zeros((B,), jnp.int32)
    if route == "kernel_with_bias":
        kw["attn_bias"] = jnp.zeros((1, h, L, L))
    if route == "materialized_bias":  # one bias per three heads
        kw["attn_bias"] = jnp.zeros((B * h // 3, L, L))
    if route == "dropout_off_tpu":
        kw.update(train=True, rngs={"dropout": jax.random.PRNGKey(1)})
    m.apply(params, x, **kw)
    assert made == (["_heads_out_product", "_heads_in_product"] if fused
                    else [])
    if route != "ring_requested":
        assert bool(ran) == fused


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_one_decode_step_matches_the_flat_projections(shape, bias, dtype):
    m, h, params, x = _setup(shape, bias, dtype)
    d = SHAPES[shape][1]
    token = x[:, :1]
    kc, vc = jax.random.normal(jax.random.PRNGKey(5), (2, B, h, L, d), dtype)
    positions = jnp.array([3, 90], jnp.int32)
    out_new, rows_new = m.apply(
        params, token, cache_kv=(kc, vc), cache_positions=positions)
    out_old, rows_old = _flat_self_attention(
        params, token, h, cache=((kc, vc), positions))
    assert out_new.shape == (B, 1, h * d)
    _assert_close(out_new, out_old, dtype, "decode output")
    _assert_trees_close(rows_new, rows_old, dtype, "new cache rows")


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_cross_attention_matches_the_flat_projections(shape, bias):
    h, d = SHAPES[shape]
    e = h * d
    m = CrossMultiheadAttention(e, h, dropout=0.0, bias=bias)
    kq, kk, kv, kp = jax.random.split(jax.random.PRNGKey(7), 4)
    query = jax.random.normal(kq, (B, L, e))
    key = jax.random.normal(kk, (B, 2 * L, e))
    value = jax.random.normal(kv, (B, 2 * L, e))
    params = m.init(kp, query, key, value)

    def old(params, query, key, value):
        p = params["params"]
        q = _split_heads(_flat_dense(p["q_proj"], query), h) * d ** -0.5
        k = _split_heads(_flat_dense(p["k_proj"], key), h)
        v = _split_heads(_flat_dense(p["v_proj"], value), h)
        o, _, _ = mha._attend(None, q, k, v, None, None, 0.0, True, False,
                              True)
        return _flat_dense(p["out_proj"], _merge_heads(o))

    def loss(f):
        return lambda *a: _scalar(f(*a))

    args = (params, query, key, value)
    _assert_close(m.apply(*args, train=True), old(*args), jnp.float32,
                  "output")
    _assert_trees_close(
        jax.grad(loss(lambda *a: m.apply(*a, train=True)),
                 argnums=(0, 1, 2, 3))(*args),
        jax.grad(loss(old), argnums=(0, 1, 2, 3))(*args),
        jnp.float32, "gradient")


def _tree_signature(params):
    return {
        jax.tree_util.keystr(path): (a.shape, str(a.dtype))
        for path, a in jax.tree_util.tree_leaves_with_path(params)
    }


def _dense_signature(prefix, rows, cols, bias):
    sig = {f"['params']['{prefix}']['kernel']": ((rows, cols), "float32")}
    if bias:
        sig[f"['params']['{prefix}']['bias']"] = ((cols,), "float32")
    return sig


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_parameter_tree_is_the_parents(shape, bias):
    """Names, shapes, dtypes — and the values ``init`` draws — as ``nn.Dense``
    over a flat ``(B, L, E)`` gave them: checkpoints, the tensor-parallel
    rules and ``quant.calibrate.prepare`` see what they saw."""
    h, d = SHAPES[shape]
    e = h * d
    x = jnp.zeros((B, L, e), jnp.bfloat16)
    key = jax.random.PRNGKey(11)

    got = SelfMultiheadAttention(e, h, bias=bias).init(key, x)
    assert _tree_signature(got) == {
        **_dense_signature("in_proj", e, 3 * e, bias),
        **_dense_signature("out_proj", e, e, bias),
    }

    class Flat(flax.linen.Module):
        @flax.linen.compact
        def __call__(self, x):
            dense = lambda n, name: flax.linen.Dense(
                n, use_bias=bias, name=name, param_dtype=jnp.float32,
                kernel_init=flax.linen.initializers.normal(0.02))
            return dense(e, "out_proj")(dense(3 * e, "in_proj")(x)[..., :e])

    want = Flat().init(key, x)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))

    cross = CrossMultiheadAttention(e, h, bias=bias).init(key, x, x, x)
    assert _tree_signature(cross) == {
        k: v for name in ("q_proj", "k_proj", "v_proj", "out_proj")
        for k, v in _dense_signature(name, e, e, bias).items()
    }


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_tree_saved_by_the_parent_loads(shape):
    """A parameter tree as the parent wrote it (flat kernels, serialized)
    restores into this module's own tree and gives the parent's output."""
    h, d = SHAPES[shape]
    e = h * d
    keys = jax.random.split(jax.random.PRNGKey(13), 5)
    saved = {"params": {
        "in_proj": {"kernel": 0.02 * jax.random.normal(keys[0], (e, 3 * e)),
                    "bias": 0.1 * jax.random.normal(keys[1], (3 * e,))},
        "out_proj": {"kernel": 0.02 * jax.random.normal(keys[2], (e, e)),
                     "bias": 0.1 * jax.random.normal(keys[3], (e,))},
    }}
    blob = flax.serialization.to_bytes(saved)

    m = SelfMultiheadAttention(e, h, dropout=0.0)
    x = jax.random.normal(keys[4], (B, L, e))
    loaded = flax.serialization.from_bytes(m.init(keys[4], x), blob)
    _assert_close(m.apply(loaded, x), _flat_self_attention(saved, x, h),
                  jnp.float32, "output")


def test_in_proj_backward_rounds_dx_once():
    """``dx`` of the head-major ``in_proj`` is one contraction over
    ``(T, H, D)``, rounded to the compute dtype once as the flat product's
    is — not three bf16 products added in bf16, which autodiff of the three
    forward products would give."""
    h, d, e = 12, 64, 768
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(kx, (B, L, e), jnp.bfloat16)
    w = (0.02 * jax.random.normal(kw, (e, 3, h, d))).astype(jnp.bfloat16)
    dys = tuple(jax.random.normal(kd, (3, B, h, L, d), jnp.bfloat16))

    _, vjp = jax.vjp(lambda x: dense._head_products(x, w, None), x)
    (dx,) = vjp(dys)
    exact = jnp.einsum("tbhld,ethd->ble", jnp.stack(dys).astype(jnp.float32),
                       w.astype(jnp.float32))
    assert dx.dtype == jnp.bfloat16
    # one rounding: every entry within half a bf16 ulp of its exact value
    # (8 bits of significand), give or take the fp32 accumulation's order
    gap = jnp.abs(dx.astype(jnp.float32) - exact)
    half_ulp = jnp.abs(exact) * 2.0 ** -8
    assert bool(jnp.all(gap <= half_ulp + 1e-6)), float(
        jnp.max(gap / (jnp.abs(exact) + 1e-6)))
    # the three-product sum this replaces does not meet it
    summed = sum(
        jnp.einsum("bhld,ehd->ble", dy, w[:, i]) for i, dy in enumerate(dys))
    assert not bool(jnp.all(
        jnp.abs(summed.astype(jnp.float32) - exact) <= half_ulp + 1e-6))


def test_a_site_without_a_quantize_mode_sows_no_calibration():
    """``calibrate.prepare`` turns every site that sowed into ``kernel_q``,
    which only a site built with a mode reads: cross-attention's projections
    (no mode) stay out of the calibration collection, a site with one sows."""
    from unicore_tpu import quant

    h, d = SHAPES["h12_d64"]
    x = jnp.ones((B, L, h * d))
    with quant.calibration_scope():
        m = CrossMultiheadAttention(h * d, h, dropout=0.0)
        params = m.init(jax.random.PRNGKey(0), x, x, x)
        _, state = m.apply(params, x, x, x,
                           mutable=[dense.CALIB_COLLECTION])
        assert not state.get(dense.CALIB_COLLECTION)
        site = dense.QuantDense(8, quantize="int8")
        _, state = site.apply(site.init(jax.random.PRNGKey(0), x), x,
                              mutable=[dense.CALIB_COLLECTION])
        assert set(state[dense.CALIB_COLLECTION]) == {"act_absmax"}
