"""What the hybrid decoder's rematerialization keeps (``hybrid_decoder.
_remat``: one policy, the arrays the layer kinds name).  At sizes a CPU
holds: the values are those of no rematerialization and of the bare
``nn.remat`` the decoder had before, bit for bit; the backward pass of an
expert layer runs no second router product, ``top_k``, ``latent_down``,
routed forward loop, ``shared_fc1`` or ``shared_fc2``, and a Mamba layer's
no second ``in_proj``; a pattern whose kinds name nothing (``AFAF``) traces
to the bare form's program, and one whose kinds give neither of the two
names newest on the list (``SRGR`` with no shared expert) to the program it
traced before they were listed; no name is dead on either side."""

import collections
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from unicore_tpu.modules import hybrid_decoder, latent_moe
from unicore_tpu.modules.hybrid_decoder import HybridDecoder

# every width differs from every other, so a product is known by its shapes
D, B, S = 32, 2, 24
N = B * S
SIZES = dict(embed_dim=D, norm_eps=1e-5, sizes={
    "M": dict(num_heads=4, head_dim=8, n_groups=2, state_size=20,
              conv_kernel=4, chunk_size=8),
    "*": dict(num_heads=4, num_kv_heads=2, head_dim=8),
    "E": dict(latent_dim=24, expert_dim=28, shared_dim=40, n_routed=12,
              top_k=3, n_held=4, first_held=4, routed_scale=2.5),
    "A": dict(num_heads=2, head_dim=16, window_size=8, chunk_size=4,
              rope_theta=1e4),
    "F": dict(ffn_dim=48, row_chunk=16),
    "S": dict(num_heads=4, num_kv_heads=2, head_dim=8, window=8,
              rope=dict(rope_theta=1e4)),
    "G": dict(num_heads=4, num_kv_heads=2, head_dim=8,
              rope=dict(rope_theta=1e4)),
    "R": dict(expert_dim=20, n_routed=10, top_k=3, n_held=5,
              first_held=5, routed_scale=2.5, shared_dim=44),
    "L": dict(num_heads=4, q_lora_rank=24, kv_lora_rank=16,
              qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
              rope=dict(rope_theta=1e4)),
})
#: the right-hand shapes of an ``E`` layer's forward products over its
#: tokens, and of the Mamba layer's ``in_proj`` (2 x 32 + 2 x 40 + 4 wide)
PRODUCTS = dict(router=(D, 12), latent_down=(D, 24), latent_up=(24, D),
                shared_fc1=(D, 40), shared_fc2=(40, D), in_proj=(D, 148))


def decoder(pattern, remat=True, shared=True):
    """``shared`` false: ``R`` without its shared expert (``mellum``'s)."""
    sizes = SIZES if shared else dict(SIZES, sizes=dict(
        SIZES["sizes"], R=dict(SIZES["sizes"]["R"], shared_dim=0)))
    return HybridDecoder(pattern=pattern, remat=remat, **sizes)


@functools.lru_cache
def inputs(pattern, dtype, shared=True):
    x = jax.random.normal(jax.random.key(0), (B, S, D), dtype)
    params = decoder(pattern, shared=shared).init(jax.random.key(1), x)
    # off the initial point: a router that spreads its choices, norms off 1
    params = jax.tree_util.tree_map(
        lambda a: (a + 0.3 * jax.random.normal(jax.random.key(2), a.shape)
                   ).astype(dtype), params)
    return params, x


def loss_of(model):
    def loss(params, x):
        y, stats = model.apply(params, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32))), stats
    return loss


def make_bare(monkeypatch):
    """The decoder as it was: ``nn.remat`` with no policy."""
    monkeypatch.setattr(hybrid_decoder, "_remat", nn.remat)


def run(pattern, dtype, remat):
    """Compiled to round wherever the program says so.  Left its default
    licence (``xla_allow_excess_precision``), XLA on the CPU makes a
    bfloat16 product in float32 and skips the rounding where the result is
    widened again at once (``R``'s shared expert: its gate is evaluated in
    float32), in the program that consumes the product where it is made and
    not in the one that reads it back: the compiler's choice, which no form
    of rematerialization states."""
    params, x = inputs(pattern, dtype)
    (value, stats), grads = jax.jit(jax.value_and_grad(
        loss_of(decoder(pattern, remat)), argnums=(0, 1), has_aux=True,
    )).lower(params, x).compile(
        compiler_options={"xla_allow_excess_precision": False})(params, x)
    return jax.tree_util.tree_leaves((value, stats, grads))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", ["*EMEM", "*EE", "AFAF", "GRSRSR"])
@pytest.mark.parametrize("other", ["no_remat", "bare_remat"])
def test_keeping_named_arrays_changes_no_bit(pattern, dtype, other,
                                             monkeypatch):
    """Loss, routing stats and every gradient, with the decoder's policy,
    without rematerialization and under a bare ``nn.remat``."""
    got = run(pattern, dtype, True)
    if other == "bare_remat":
        make_bare(monkeypatch)
    want = run(pattern, dtype, other == "bare_remat")
    assert len(got) == len(want) > 10
    # a scanned Mamba layer's float32 gradients differ in their last bits
    # between a rematerialized scan and a plain one, under the bare form
    # too (``MM`` alone shows it): there, close; everywhere else, equal
    exact = (other, dtype, "M" in pattern) != ("no_remat", jnp.float32, True)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        if exact:
            assert bool(jnp.all(g == w))
        else:
            assert jnp.allclose(g, w, rtol=1e-4, atol=1e-4)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in got)
    if "E" in pattern or "R" in pattern:
        assert float(got[1][latent_moe.STATS.index("pairs_here")]) > 0


def equations(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the programs its equations hold,
    each with the names of the primitives it lies inside."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub, inside + (eqn.primitive.name,))


def backward(pattern, shared=True):
    params, x = inputs(pattern, jnp.float32, shared)
    model = decoder(pattern, shared=shared)
    grad = jax.grad(lambda p, x: loss_of(model)(p, x)[0], (0, 1))
    return jax.make_jaxpr(grad)(params, x).jaxpr


def remat_primitive():
    """What this JAX calls the equation ``jax.checkpoint`` leaves."""
    return jax.make_jaxpr(jax.checkpoint(jnp.sin))(1.0).eqns[0].primitive.name


def rematerialized(jaxpr):
    """What the backward pass runs inside its rematerializing equations
    (the layers' forward made again, and their transposes): forward
    products over the tokens, ``(N, D)`` or ``(B, S, D)``, by right-hand
    shape, and other primitives by name."""
    found = collections.Counter()
    remat = remat_primitive()
    for eqn, inside in equations(jaxpr):
        if remat not in inside:
            continue
        name = eqn.primitive.name
        if name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            (lc, rc), _ = eqn.params["dimension_numbers"]
            if (math.prod(lhs[:-1]) == N and len(rhs) == 2
                    and (lc, rc) == ((len(lhs) - 1,), (0,))):
                name = rhs
        found[name] += 1
    return found


def test_the_backward_pass_makes_no_kept_array_again(monkeypatch):
    """``*EMEM`` is ``*`` and one scanned ``EM`` body, so each count is one
    ``E`` layer's and one ``M`` layer's.  Against the bare form the
    rematerialized part loses the router's product and ``top_k``,
    ``latent_down``, ``shared_fc1``, ``shared_fc2``, the routed experts'
    forward loop, the layout's sort and the Mamba layer's ``in_proj``;
    ``latent_up`` is still made again, once."""
    kept = rematerialized(backward("*EMEM"))
    make_bare(monkeypatch)
    bare = rematerialized(backward("*EMEM"))
    named = ("router", "latent_down", "shared_fc1", "shared_fc2", "in_proj")
    for name in named:
        assert (bare[PRODUCTS[name]], kept[PRODUCTS[name]]) == (1, 0), name
    # left on purpose: the small one that makes the next layer's input
    # (latent_moe.KEPT says why)
    assert (bare[PRODUCTS["latent_up"]], kept[PRODUCTS["latent_up"]]) == (1, 1)
    assert (bare["top_k"], kept["top_k"]) == (1, 0)
    # the layout's stable sort of the pairs
    assert (bare["sort"], kept["sort"]) == (1, 0)
    # the loops over the tiles in use: the forward one goes, the backward
    # one stays
    assert (bare["while"], kept["while"]) == (2, 1)
    # nothing else is kept (the Mamba layer's other products and the
    # attention layer's are made again as they were) and nothing is added
    gone = bare - kept
    assert {k for k in gone if isinstance(k, tuple)} == {
        PRODUCTS[name] for name in named}
    assert gone["dot_general"] == 2      # the forward loop's two, per tile
    assert not kept - bare


def listing(jaxpr):
    return [
        (eqn.primitive.name, inside,
         tuple(str(v.aval) for v in eqn.invars),
         tuple(str(v.aval) for v in eqn.outvars))
        for eqn, inside in equations(jaxpr)
    ]


def test_a_pattern_that_names_nothing_traces_to_the_bare_program(monkeypatch):
    """``AFAF`` (``evabyte``'s kinds): with the policy the backward pass is
    the bare ``nn.remat``'s, equation for equation."""
    kept = listing(backward("AFAF"))
    make_bare(monkeypatch)
    bare = listing(backward("AFAF"))
    assert len(kept) > 100 and remat_primitive() in {e[0] for e in kept}
    assert kept == bare


def names_in(jaxpr):
    return {eqn.params["name"] for eqn, _ in equations(jaxpr)
            if eqn.primitive.name == "name"}


def test_no_name_is_dead_on_either_side():
    """Every name the decoder's policy lists is given to an array by some
    layer kind, and every array a layer names is on the policy's list."""
    # ``R`` with its shared expert beside ``E``: the gated form's names too;
    # ``L``: its two normed latents and its rotary key
    params, x = inputs("*EMAFRL", jnp.float32)
    produced = names_in(
        jax.make_jaxpr(decoder("*EMAFRL").apply)(params, x).jaxpr)
    assert produced == set(hybrid_decoder.KEPT)
    assert len(set(hybrid_decoder.KEPT)) == len(hybrid_decoder.KEPT)


def test_kinds_that_give_neither_new_name_trace_as_before(monkeypatch):
    """``SRGR`` with no shared expert (``mellum``'s kinds): ``R`` names its
    router's arrays, but neither ``shared_fc1``'s product nor ``in_proj``'s,
    so the backward pass is, equation for equation, what the policy gave
    before it listed those two."""
    new = {"moe_shared_fc1", "mamba_in_proj"}
    assert new < set(hybrid_decoder.KEPT)
    now = backward("SRGR", shared=False)
    given = names_in(now)
    assert given and not new & given
    monkeypatch.setattr(
        hybrid_decoder, "KEPT",
        tuple(k for k in hybrid_decoder.KEPT if k not in new))
    before = listing(backward("SRGR", shared=False))
    assert len(before) > 100 and remat_primitive() in {e[0] for e in before}
    assert listing(now) == before
