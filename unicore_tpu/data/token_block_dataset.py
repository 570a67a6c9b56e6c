"""Documents packed into fixed blocks of tokens (the usual pretraining
layout: Megatron-LM's GPT dataset, fairseq's ``TokenBlockDataset`` in
``none`` mode).

The epoch's documents, in an order drawn from ``(seed, epoch)``, are joined
end to end (each document already carries the tokenizer's end token) and
cut into blocks of ``block_size`` tokens in that order.  Nothing marks the
joins and nothing is masked or reset at them; the last, short block of an
epoch is dropped.  Within an epoch no token appears twice and only the
fewer than ``block_size`` tokens of that last block are left out.

Document lengths have to be known before the first block can be cut, so
the constructor reads every document once (``sizes``; the tokenizer
releases the interpreter lock, so a few threads share the work).  Blocks
are built lazily, on whichever thread asks (a data worker), under a
``unicore:data_pack`` annotation.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import data_utils
from .base_wrapper_dataset import BaseWrapperDataset


def document_sizes(dataset, threads=8):
    """Token count of every document of ``dataset``."""
    with ThreadPoolExecutor(threads) as pool:
        return np.fromiter(
            pool.map(lambda i: len(dataset[i]), range(len(dataset))),
            dtype=np.int64, count=len(dataset),
        )


class TokenBlockDataset(BaseWrapperDataset):
    def __init__(self, dataset, block_size, seed, sizes=None):
        super().__init__(dataset)
        self.block_size = int(block_size)
        self.seed = seed
        self.sizes = (
            document_sizes(dataset) if sizes is None
            else np.asarray(sizes, np.int64)
        )
        if int(self.sizes.sum()) < self.block_size:
            raise ValueError(
                f"{int(self.sizes.sum())} tokens do not fill one block of "
                f"{self.block_size}"
            )
        self.set_epoch(1)

    def set_epoch(self, epoch):
        super().set_epoch(epoch)
        with data_utils.numpy_seed(self.seed + epoch - 1):
            self._order = np.random.permutation(len(self.sizes))
        # _ends[j]: tokens of the epoch's stream up to the end of its j-th
        # document
        self._ends = np.cumsum(self.sizes[self._order])

    def __len__(self):
        return int(self._ends[-1]) // self.block_size

    def ordered_indices(self):
        return np.arange(len(self))

    def num_tokens(self, index):
        return self.block_size

    def size(self, index):
        return self.block_size

    def ordered_sizes(self):
        return np.full(len(self), self.block_size, np.int64)

    @property
    def can_reuse_epoch_itr_across_epochs(self):
        return False  # the documents' order changes with the epoch

    def __getitem__(self, index):
        from unicore_tpu.telemetry import spans

        with spans.annotation("data_pack", block=int(index)):
            start = index * self.block_size
            stop = start + self.block_size
            first = int(np.searchsorted(self._ends, start, side="right"))
            last = int(np.searchsorted(self._ends, stop, side="left"))
            parts = [
                np.asarray(self.dataset[int(self._order[j])])
                for j in range(first, last + 1)
            ]
            begin = start - (int(self._ends[first - 1]) if first else 0)
            return np.concatenate(parts)[begin:begin + self.block_size]
