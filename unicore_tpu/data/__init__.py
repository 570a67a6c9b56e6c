"""Data pipeline (reference /root/reference/unicore/data/__init__.py:9-34)."""

from .unicore_dataset import UnicoreDataset, EpochListening
from .base_wrapper_dataset import BaseWrapperDataset

from . import data_utils
from .dictionary import Dictionary
from .lru_cache_dataset import LRUCacheDataset
from .mask_tokens_dataset import MaskTokensDataset
from .bert_tokenize_dataset import BertTokenizeDataset
from .byte_tokenize_dataset import ByteDictionary, ByteTokenizeDataset
from .misc_datasets import (
    AppendTokenDataset,
    FromNumpyDataset,
    NumSamplesDataset,
    NumelDataset,
    PrependTokenDataset,
    RawArrayDataset,
    RawLabelDataset,
    RawNumpyDataset,
    TokenizeDataset,
)
from .nested_dictionary_dataset import NestedDictionaryDataset
from .pad_dataset import (
    FixedPadDataset,
    LeftPadDataset,
    PadDataset,
    RightPadDataset,
    RightPadDataset2D,
)
from .lmdb_dataset import LMDBDataset
from .indexed_dataset import IndexedPickleDataset, IndexedPickleDatasetBuilder, make_builder
from .sort_dataset import SortDataset, EpochShuffleDataset
from .token_block_dataset import TokenBlockDataset

from .iterators import (
    BufferedIterator,
    CountingIterator,
    EpochBatchIterator,
    GroupedIterator,
    ShardedIterator,
)
from .prefetch import DevicePrefetcher, PreparedUpdate, RawUpdate

__all__ = [
    "AppendTokenDataset",
    "BaseWrapperDataset",
    "BertTokenizeDataset",
    "BufferedIterator",
    "ByteDictionary",
    "ByteTokenizeDataset",
    "CountingIterator",
    "Dictionary",
    "EpochBatchIterator",
    "EpochListening",
    "EpochShuffleDataset",
    "FixedPadDataset",
    "FromNumpyDataset",
    "GroupedIterator",
    "IndexedPickleDataset",
    "IndexedPickleDatasetBuilder",
    "LMDBDataset",
    "LRUCacheDataset",
    "LeftPadDataset",
    "MaskTokensDataset",
    "NestedDictionaryDataset",
    "NumSamplesDataset",
    "NumelDataset",
    "PadDataset",
    "PrependTokenDataset",
    "RawArrayDataset",
    "RawLabelDataset",
    "RawNumpyDataset",
    "RightPadDataset",
    "RightPadDataset2D",
    "ShardedIterator",
    "SortDataset",
    "TokenBlockDataset",
    "TokenizeDataset",
    "UnicoreDataset",
    "data_utils",
]
