"""Causal-LM task: the BERT data pipeline minus the masking stage.

Same shards, tokenizer, padding/bucketing discipline as tasks/bert.py —
``target`` is simply the input token stream and the ``lm_cross_entropy``
loss shifts it by one (next-token prediction).  Exists so the
incremental-decode serving path (models/transformer_lm.py,
docs/serving.md "Incremental decode") has a trainable decoder-only
checkpoint behind it, end-to-end from ``examples/bert/make_example_data.py``
text.

With ``--tokens-per-sample N`` the documents are not padded one per row but
packed: joined end to end and cut into blocks of exactly ``N`` tokens
(data/token_block_dataset.py), one block per row, every row full.

With ``--tokenizer bytes`` a document is its UTF-8 bytes and the end id
(data/byte_tokenize_dataset.py: 320 ids, no ``dict.txt``); shards, packing
and padding are the same.
"""

import logging
import os

from unicore_tpu.data import (
    BertTokenizeDataset,
    ByteDictionary,
    ByteTokenizeDataset,
    Dictionary,
    EpochShuffleDataset,
    LRUCacheDataset,
    NestedDictionaryDataset,
    RightPadDataset,
    TokenBlockDataset,
)
from unicore_tpu.tasks import register_task
from unicore_tpu.tasks.bert import open_text_dataset
from unicore_tpu.tasks.unicore_task import UnicoreTask

logger = logging.getLogger(__name__)


@register_task("causal_lm")
class CausalLMTask(UnicoreTask):
    """Next-token-prediction over the same corpora the BERT task reads."""

    @staticmethod
    def add_args(parser):
        parser.add_argument(
            "data",
            help="colon separated path to data directories list, "
                 "iterated upon during epochs in round-robin manner",
        )
        parser.add_argument(
            "--seq-pad-multiple", default=8, type=int,
            help="pad batch sequence lengths to this multiple; 128 aligns "
                 "batches with the flash-attention kernel's block size",
        )

        parser.add_argument(
            "--tokens-per-sample", default=0, type=int,
            help="pack documents into blocks of this many tokens (joined "
                 "by the end token, cut in order, the epoch's last short "
                 "block dropped; no mask and no state reset at the joins); "
                 "0 keeps one document per row, cut at --max-seq-len",
        )

        parser.add_argument(
            "--tokenizer", default="wordpiece", choices=("wordpiece", "bytes"),
            help="wordpiece: BERT's, over the data directory's dict.txt; "
                 "bytes: a document's UTF-8 bytes, 320 ids, no dict.txt",
        )

    def __init__(self, args, dictionary):
        super().__init__(args)
        self.dictionary = dictionary
        self.seed = args.seed

    @classmethod
    def setup_task(cls, args, **kwargs):
        if getattr(args, "tokenizer", "wordpiece") == "bytes":
            return cls(args, ByteDictionary())
        dictionary = Dictionary.load(os.path.join(args.data, "dict.txt"))
        logger.info(f"dictionary: {len(dictionary)} types")
        return cls(args, dictionary)

    def _padded(self, dataset):
        return RightPadDataset(
            dataset,
            pad_idx=self.dictionary.pad(),
            pad_to_multiple=self.args.seq_pad_multiple,
            pad_to_buckets=self.length_bucket_edges(),
        )

    def load_dataset(self, split, combine=False, **kwargs):
        a = self.args
        block = getattr(a, "tokens_per_sample", 0)
        text = open_text_dataset(os.path.join(a.data, split))
        # a document is cut to the model's positions only where it is a row
        # of its own
        max_seq_len = None if block else a.max_seq_len
        if isinstance(self.dictionary, ByteDictionary):
            tokens = ByteTokenizeDataset(text, max_seq_len=max_seq_len)
        else:
            tokens = BertTokenizeDataset(
                text, os.path.join(a.data, "dict.txt"),
                max_seq_len=max_seq_len,
            )
        if block:
            # input and target read the same block: built once
            tokens = LRUCacheDataset(
                TokenBlockDataset(tokens, block, self.seed)
            )
        batches = NestedDictionaryDataset(
            {
                "net_input": {"src_tokens": self._padded(tokens)},
                "target": self._padded(tokens),
            }
        )
        if split == "train" and not block:  # packing draws its own order
            batches = EpochShuffleDataset(batches, len(batches), self.seed)
        self.datasets[split] = batches
