"""Tokenizer-free text: a document is its UTF-8 bytes.

Ids ``0 .. BYTE_OFFSET - 1`` are special (pad, begin, end and reserved
sentinels), byte ``b`` is id ``BYTE_OFFSET + b``: 320 ids in all, the
vocabulary of byte-level models of the EvaByte kind.
"""

import numpy as np

from .base_wrapper_dataset import BaseWrapperDataset

BYTE_OFFSET = 64
#: the special ids in use, by position; the rest up to BYTE_OFFSET are reserved
SPECIALS = ("<pad>", "<bos>", "<eos>", "<unk>")


class ByteDictionary:
    """What a task asks of its dictionary (``pad``, ``eos``, a length),
    and the mapping itself: nothing to load, nothing to learn."""

    def __len__(self):
        return BYTE_OFFSET + 256

    def pad(self):
        return SPECIALS.index("<pad>")

    def bos(self):
        return SPECIALS.index("<bos>")

    def eos(self):
        return SPECIALS.index("<eos>")

    def unk(self):
        return SPECIALS.index("<unk>")

    def encode(self, text):
        """``text`` -> its bytes' ids followed by the end id (int64)."""
        ids = np.frombuffer(text.encode("utf-8"), np.uint8).astype(np.int64)
        return np.append(ids + BYTE_OFFSET, self.eos())

    def decode(self, ids):
        """The text of the byte ids among ``ids``; special ids are left
        out."""
        ids = np.asarray(ids, np.int64)
        raw = (ids[ids >= BYTE_OFFSET] - BYTE_OFFSET).astype(np.uint8)
        return raw.tobytes().decode("utf-8", errors="replace")


class ByteTokenizeDataset(BaseWrapperDataset):
    def __init__(self, dataset, max_seq_len=None):
        """``max_seq_len``: documents are cut to that many ids; ``None``
        keeps them whole (for a consumer that packs them into blocks)."""
        super().__init__(dataset)
        self.dictionary = ByteDictionary()
        self.max_seq_len = max_seq_len

    @property
    def can_reuse_epoch_itr_across_epochs(self):
        return True  # tokenization is epoch-independent

    def __getitem__(self, index: int):
        return self.dictionary.encode(self.dataset[index])[: self.max_seq_len]
