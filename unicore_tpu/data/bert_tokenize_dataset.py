"""WordPiece tokenization view over a dataset of raw strings.

Parity surface (reference
/root/reference/unicore/data/bert_tokenize_dataset.py:12); gated on the
optional ``tokenizers`` package.
"""

import numpy as np

from .base_wrapper_dataset import BaseWrapperDataset

try:
    from tokenizers import BertWordPieceTokenizer
except ImportError:
    BertWordPieceTokenizer = None


class BertTokenizeDataset(BaseWrapperDataset):
    def __init__(self, dataset, dict_path: str, max_seq_len=512):
        """``max_seq_len``: documents are cut to that many tokens; ``None``
        keeps them whole (for a consumer that packs them into blocks)."""
        if BertWordPieceTokenizer is None:
            raise ImportError(
                "BertTokenizeDataset requires the 'tokenizers' package"
            )
        self.dataset = dataset
        self.tokenizer = BertWordPieceTokenizer(dict_path, lowercase=True)
        self.max_seq_len = max_seq_len

    @property
    def can_reuse_epoch_itr_across_epochs(self):
        return True  # tokenization is epoch-independent

    def __getitem__(self, index: int):
        text = self.dataset[index].replace("<unk>", "[UNK]")
        ids = np.asarray(self.tokenizer.encode(text).ids, dtype=np.int64)
        return ids[: self.max_seq_len]
