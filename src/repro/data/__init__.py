"""Data substrate: synthetic generators (paper protocols), sharded pipeline,
the device-resident corpus store, and the dataset-search sketch index (the
paper's §1.3 application)."""
from .dataset_search import DatasetSearchIndex, SearchResult, TableSketch
from .families import (FAMILY_NAMES, ComponentSpec, CSFamily, ICWSFamily,
                       JLFamily, PSFamily, TSFamily, make_family,
                       wmh_storage)
from .ingest import (pad_linear_batch, pad_sample_batch, pad_sparse_batch,
                     sketch_batch)
from .merge import (build_sharded, merge_stores, partition_by_key,
                    split_by_key)
from .pipeline import TokenPipeline
from .store import CorpusStore
from .synthetic import (kurtosis, sparse_pair, tfidf_corpus, token_stream,
                        worldbank_like_pair)

__all__ = ["DatasetSearchIndex", "SearchResult", "TableSketch",
           "CorpusStore", "sketch_batch", "pad_sparse_batch",
           "pad_linear_batch", "pad_sample_batch",
           "FAMILY_NAMES", "ComponentSpec", "ICWSFamily", "CSFamily",
           "JLFamily", "TSFamily", "PSFamily", "make_family", "wmh_storage",
           "build_sharded", "merge_stores", "partition_by_key",
           "split_by_key",
           "TokenPipeline", "sparse_pair", "worldbank_like_pair", "kurtosis",
           "tfidf_corpus", "token_stream"]
