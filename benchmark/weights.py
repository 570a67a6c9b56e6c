"""Weights from the seed, made by the benchmark and handed to both sides.

The program's own initializers are not used: the benchmark asks the program
only for the *shapes* of its parameter tree (``jax.eval_shape``), fills them
here in one jitted call on the device, and gives the same numbers to the
program (through its model's ``init_params``) and to the plain reference.

Rules, first match on the leaf's ``/``-joined path:

* ``gbf/mul`` -> 1, ``gbf/bias`` -> 0, ``means`` / ``stds`` -> U(0, 3)
  (Uni-Mol's Gaussian basis as its paper's code initialises it);
* a leaf named ``weight`` or ``scale`` (norm gains) -> 1 + N(0, 0.02);
* a leaf named ``bias`` -> N(0, 0.02) (not zero: a bias that is zero hides a
  reference that forgets it);
* everything else (kernels, embeddings) -> N(0, 0.02), BERT's recipe.
"""

import jax
import jax.numpy as jnp


def _path(path):
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _rule(name):
    leaf = name.rsplit("/", 1)[-1]
    if "gbf/mul" in name:
        return "one"
    if "gbf/bias" in name:
        return "zero"
    if leaf in ("means", "stds"):
        return "uniform3"
    if leaf in ("weight", "scale"):
        return "gain"
    return "normal"


def fold_seed(seed):
    """Any whole number -> a value an int32 holds (the trainer's step
    scalars carry the seed as int32; the driver's seeds pass 2**31)."""
    return int(seed) % 2147483629


def make(shapes, seed):
    """``shapes``: a pytree of ShapeDtypeStruct (or arrays).  Returns a
    float32 tree of the same structure, made on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in leaves]
    specs = [(tuple(leaf.shape), _rule(n)) for n, (_, leaf) in zip(names, leaves)]

    @jax.jit
    def build(key):
        out = []
        for i, (shape, rule) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if rule == "one":
                out.append(jnp.ones(shape, jnp.float32))
            elif rule == "zero":
                out.append(jnp.zeros(shape, jnp.float32))
            elif rule == "uniform3":
                out.append(jax.random.uniform(k, shape, jnp.float32, 0.0, 3.0))
            elif rule == "gain":
                out.append(1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32))
            else:
                out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        return out

    # threefry on every backend: the same seed gives the same weights to
    # the program and, later in the process, to the reference
    key = jax.random.key(fold_seed(seed), impl="threefry2x32")
    return jax.tree_util.tree_unflatten(treedef, build(key))


def leaf_names(tree):
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [_path(p) for p, _ in leaves]
