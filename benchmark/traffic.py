"""The one general generator of traffic.  A cell's file under
``benchmark/workloads/`` holds parameters only; the kinds below read them.

Every seed gets the same multiset of sizes (document lengths, atom counts),
in another order and with other contents: a seed changes which work comes
when, never how much work there is.
"""

import os
from statistics import NormalDist

import numpy as np

SPECIALS = ["[PAD]", "[UNK]", "[CLS]", "[SEP]"]

#: the 26 element symbols of the conformer corpus, commonest first
ELEMENTS = [
    "C", "H", "O", "N", "S", "F", "Cl", "Br", "P", "I", "B", "Si", "Se",
    "Na", "K", "Li", "Mg", "Ca", "Fe", "Zn", "Cu", "Al", "As", "Sn", "Co",
    "Ni",
]


def _rng(seed, salt):
    return np.random.default_rng([int(seed), int(salt)])


def even_sizes(lo, hi, n):
    """``n`` whole sizes spread evenly over [lo, hi]."""
    return np.round(np.linspace(lo, hi, n)).astype(np.int64)


def lognormal_sizes(median, sigma, lo, hi, n):
    """``n`` whole sizes at the evenly spaced quantiles of a log-normal
    (median, sigma of the log), clipped to [lo, hi]: the distribution
    itself, not a sample of it, so every seed sees the same set."""
    nd = NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in q])
    sizes = np.round(median * np.exp(sigma * z)).astype(np.int64)
    return np.clip(sizes, lo, hi)


def zipf_p(n):
    p = 1.0 / np.arange(1, n + 1)
    return p / p.sum()


def _word(i):
    """Distinct all-letter words: WordPiece keeps each whole."""
    s = ""
    for _ in range(4):
        s = "abcdefghijklmnopqrstuvwxyz"[i % 26] + s
        i //= 26
    return s


def text_corpus(params, out_dir, seed):
    """Documents of Zipf-drawn words in the framework's native indexed
    shards, with a ``dict.txt`` of ``vocab`` entries (BERT's 30,522, so the
    model has its real embedding and LM head)."""
    from unicore_tpu.data.indexed_dataset import make_builder

    n_words = int(params["vocab"]) - len(SPECIALS) - 1
    words = np.array([_word(i) for i in range(n_words)])
    with open(os.path.join(out_dir, "dict.txt"), "w") as f:
        f.write("\n".join(SPECIALS + ["[MASK]"] + list(words)) + "\n")
    rng = _rng(seed, 1)
    sizes = even_sizes(params["doc_words"][0], params["doc_words"][1],
                       int(params["n_docs"]))
    rng.shuffle(sizes)
    ids = rng.choice(n_words, size=int(sizes.sum()), p=zipf_p(n_words))
    # every word has four letters: a document is its words' five bytes
    # each (the word and a space) less the last space, taken in one gather
    spaced = np.array([w + " " for w in words], dtype="S5")
    builder = make_builder(os.path.join(out_dir, "train"))
    at = 0
    for n in sizes:
        builder.add_item(spaced[ids[at:at + n]].tobytes()[:-1].decode())
        at += n
    builder.finalize()
    return {"sizes": sizes}


def conformer_corpus(params, out_dir, seed):
    """Conformer records ``{"atoms", "coordinates"}`` in the native indexed
    shards: atom counts log-normal, element types Zipf over the dictionary,
    coordinates Gaussian (a blob of about the right radius)."""
    from unicore_tpu.data.indexed_dataset import make_builder

    with open(os.path.join(out_dir, "dict.txt"), "w") as f:
        f.write("\n".join(SPECIALS + ELEMENTS) + "\n")
    rng = _rng(seed, 2)
    a = params["atoms"]
    sizes = lognormal_sizes(a["median"], a["sigma"], a["min"], a["max"],
                            int(params["n_records"]))
    rng.shuffle(sizes)
    total = int(sizes.sum())
    elem = np.array(ELEMENTS, dtype=object)[
        rng.choice(len(ELEMENTS), size=total, p=zipf_p(len(ELEMENTS)))
    ]
    xyz = rng.standard_normal((total, 3)).astype(np.float32)
    builder = make_builder(os.path.join(out_dir, "train"))
    at = 0
    for n in sizes:
        radius = float(params.get("angstrom_per_cbrt_atom", 1.2)) * n ** (1 / 3)
        builder.add_item({
            "atoms": list(elem[at:at + n]),
            "coordinates": xyz[at:at + n] * radius,
        })
        at += n
    builder.finalize()
    return {"sizes": sizes}


CORPORA = {"text": text_corpus, "conformers": conformer_corpus}


def write_corpus(params, out_dir, seed):
    try:
        kind = CORPORA[params["kind"]]
    except KeyError:
        raise ValueError(
            f"unknown corpus kind {params.get('kind')!r}; have {sorted(CORPORA)}"
        ) from None
    os.makedirs(out_dir, exist_ok=True)
    return kind(params, out_dir, seed)


def pad_to_edges(sample, edges, pad_values, length_key):
    """Right-pad every array of a collated batch whose trailing dims are
    the batch's padded length ``L`` up to the next of ``edges``, with the
    task's own pad value for that key.  ``pad_values`` maps the flattened
    key (``net_input.src_tokens``) to its value and the number of length
    axes (1: (B, L[, C]); 2: (B, L, L))."""
    cur = _get(sample, length_key).shape[1]
    edge = next((e for e in sorted(edges) if e >= cur), None)
    if edge is None:
        raise ValueError(f"batch length {cur} is beyond the last edge {edges}")
    if edge == cur:
        return sample, edge
    out = _map(sample, "", lambda key, arr: _pad(arr, cur, edge, *pad_values[key]))
    return out, edge


def fit_to_edge(sample, edge, pad_values, length_key):
    """A batch of exactly ``edge`` positions, for warming that shape: padded
    up, or cut to its first ``edge`` positions where it is longer."""
    cur = _get(sample, length_key).shape[1]
    if cur <= edge:
        return pad_to_edges(sample, [edge], pad_values, length_key)[0]

    def crop(key, arr):
        arr = np.asarray(arr)
        index = [slice(None)] * arr.ndim
        for ax in range(1, 1 + pad_values[key][1]):
            index[ax] = slice(0, edge)
        return arr[tuple(index)]

    return _map(sample, "", crop)


def _get(tree, dotted):
    for part in dotted.split("."):
        tree = tree[part]
    return tree


def _map(tree, prefix, fn):
    if isinstance(tree, dict):
        return {
            k: _map(v, f"{prefix}.{k}" if prefix else k, fn)
            for k, v in tree.items()
        }
    return fn(prefix, tree)


def _pad(arr, cur, edge, value, axes):
    arr = np.asarray(arr)
    widths = [(0, 0)] * arr.ndim
    for ax in range(1, 1 + axes):
        assert arr.shape[ax] == cur, (arr.shape, cur)
        widths[ax] = (0, edge - cur)
    return np.pad(arr, widths, constant_values=value)


def real_lengths(sample, key, pad_idx):
    """Non-padding input tokens of each row of a batch (atoms, for a
    molecule, with its two special tokens)."""
    return (np.asarray(_get(sample, key)) != pad_idx).sum(axis=1).astype(np.int64)

