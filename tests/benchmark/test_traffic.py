"""The traffic generator: deterministic in the seed, the stated
distributions, the same multiset of sizes for every seed, and the host
padding to a cell's edges."""

import os
import pickle

import numpy as np
import pytest

from benchmark import traffic, weights


def read_records(path):
    from unicore_tpu.data.indexed_dataset import IndexedPickleDataset

    ds = IndexedPickleDataset(path)
    return [ds[i] for i in range(len(ds))]


TEXT = {"kind": "text", "vocab": 300, "n_docs": 50, "doc_words": [20, 40]}
MOLS = {"kind": "conformers", "n_records": 400,
        "atoms": {"median": 48, "sigma": 0.5, "min": 8, "max": 254}}


@pytest.mark.parametrize("params", [TEXT, MOLS], ids=["text", "conformers"])
def test_same_seed_same_corpus_other_seed_same_sizes(params, tmp_path):
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a = traffic.write_corpus(params, str(tmp_path / "a"), big)
    b = traffic.write_corpus(params, str(tmp_path / "b"), big)
    c = traffic.write_corpus(params, str(tmp_path / "c"), 7)
    for name in ("train.bin", "train.idx", "dict.txt"):
        same = open(tmp_path / "a" / name, "rb").read()
        assert same == open(tmp_path / "b" / name, "rb").read()
    assert open(tmp_path / "a" / "train.bin", "rb").read() != \
        open(tmp_path / "c" / "train.bin", "rb").read()
    assert sorted(a["sizes"]) == sorted(c["sizes"])
    assert list(a["sizes"]) != list(c["sizes"])
    assert list(a["sizes"]) == list(b["sizes"])


def test_text_corpus_is_zipf_over_the_vocabulary(tmp_path):
    traffic.write_corpus(TEXT, str(tmp_path), 3)
    vocab = open(tmp_path / "dict.txt").read().split()
    assert len(vocab) == 300 and vocab[:5] == traffic.SPECIALS + ["[MASK]"]
    docs = read_records(str(tmp_path / "train"))
    lengths = [len(d.split()) for d in docs]
    assert min(lengths) == 20 and max(lengths) == 40 and len(docs) == 50
    words = " ".join(docs).split()
    counts = {w: words.count(w) for w in (vocab[5], vocab[6], vocab[24])}
    # p(rank r) ~ 1/r: the first word about twice the second, 20x the 20th
    assert counts[vocab[5]] > counts[vocab[6]] > counts[vocab[24]]


def test_conformer_sizes_follow_the_stated_log_normal(tmp_path):
    info = traffic.write_corpus(MOLS, str(tmp_path), 11)
    sizes = np.asarray(info["sizes"])
    assert sizes.min() >= 8 and sizes.max() <= 254
    assert abs(np.median(sizes) - 48) <= 1
    assert abs(np.std(np.log(sizes)) - 0.5) < 0.03
    recs = read_records(str(tmp_path / "train"))
    assert [len(r["atoms"]) for r in recs] == list(sizes)
    assert all(r["coordinates"].shape == (len(r["atoms"]), 3) for r in recs)
    atoms = [a for r in recs for a in r["atoms"]]
    assert set(atoms) <= set(traffic.ELEMENTS)
    assert atoms.count("C") > atoms.count("H") > atoms.count("N")


def test_pad_to_edges_uses_each_keys_own_value_and_axes():
    batch = {"net_input": {"tok": np.ones((2, 10), np.int64),
                           "pair": np.ones((2, 10, 10), np.float32),
                           "xyz": np.ones((2, 10, 3), np.float32)}}
    values = {"net_input.tok": (7, 1), "net_input.pair": (0.0, 2),
              "net_input.xyz": (0.0, 1)}
    out, edge = traffic.pad_to_edges(batch, [8, 16, 24], values, "net_input.tok")
    assert edge == 16
    assert out["net_input"]["tok"].shape == (2, 16)
    assert (out["net_input"]["tok"][:, 10:] == 7).all()
    assert out["net_input"]["pair"].shape == (2, 16, 16)
    assert out["net_input"]["pair"].sum() == 200
    assert out["net_input"]["xyz"].shape == (2, 16, 3)
    assert list(traffic.real_lengths(out, "net_input.tok", 7)) == [10, 10]
    cut = traffic.fit_to_edge(batch, 8, values, "net_input.tok")
    assert cut["net_input"]["pair"].shape == (2, 8, 8)
    with pytest.raises(ValueError):
        traffic.pad_to_edges(batch, [4, 8], values, "net_input.tok")


def test_weights_come_from_the_seed_alone():
    import jax
    import jax.numpy as jnp

    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    shapes = {"params": {"dense": {"kernel": s(64, 32), "bias": s(32)},
                         "layer_norm": {"weight": s(32), "bias": s(32)},
                         "gbf": {"mul": {"embedding": s(9, 1)},
                                 "bias": {"embedding": s(9, 1)},
                                 "means": s(16)}}}
    a = weights.make(shapes, 2 ** 31 + 5)
    b = weights.make(shapes, 2 ** 31 + 5)
    c = weights.make(shapes, 6)
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((a["params"]["dense"]["kernel"] == c["params"]["dense"]["kernel"]).all())
    p = a["params"]
    assert abs(float(p["dense"]["kernel"].std()) - 0.02) < 0.003
    assert abs(float(p["layer_norm"]["weight"].mean()) - 1.0) < 0.02
    assert float(p["gbf"]["mul"]["embedding"].min()) == 1.0
    assert float(jnp.abs(p["gbf"]["bias"]["embedding"]).max()) == 0.0
    assert 0.0 <= float(p["gbf"]["means"].min()) and float(p["gbf"]["means"].max()) <= 3.0
    assert weights.leaf_names(shapes)[0] == "params/dense/bias"
