"""ParIS+ core: iSAX math, the flat CSR index, search, build, distribution."""

from repro.core.index import (
    ParISIndex,
    ShardedIndex,
    assemble_index,
    build_index,
    build_sharded_index,
    build_sharded_index_on_devices,
    empty_index,
)
from repro.core.search import (
    PackedComponents,
    SearchConfig,
    SearchResult,
    approx_search,
    approx_search_batch,
    brute_force,
    exact_knn,
    exact_knn_batch,
    exact_knn_batch_packed,
    exact_search,
    exact_search_batch,
    exact_search_batch_packed,
    exact_search_single,
    make_batch_engine,
    merge_top_lists,
    nb_exact_search,
    pack_components,
)
from repro.core.build_pipeline import (
    BuildStats, PipelineBuilder, bulk_load_chunk, merge_runs,
)
from repro.core.block_cache import BlockCache, ColdReader
from repro.core.coldtier import (
    ColdShard,
    cold_exact_knn_batch,
    cold_exact_search_batch,
    cold_knn_batch_tiered,
    load_cold_shard,
    make_cold_batch_engine,
)
from repro.core.datagen import SeriesSource, random_walk
from repro.core.ingest import (
    CompactionPolicy,
    CompactionResult,
    DeltaShard,
    IngestPipeline,
    MutableIndex,
    build_delta_shard,
)

__all__ = [
    "ParISIndex", "ShardedIndex", "build_index", "assemble_index",
    "build_sharded_index", "build_sharded_index_on_devices", "empty_index",
    "PackedComponents", "SearchConfig", "SearchResult", "approx_search",
    "approx_search_batch", "brute_force", "exact_knn", "exact_knn_batch",
    "exact_knn_batch_packed", "exact_search", "exact_search_batch",
    "exact_search_batch_packed", "exact_search_single", "make_batch_engine",
    "merge_top_lists", "nb_exact_search", "pack_components",
    "BuildStats", "PipelineBuilder", "bulk_load_chunk", "merge_runs",
    "BlockCache", "ColdReader", "ColdShard", "cold_exact_knn_batch",
    "cold_exact_search_batch", "cold_knn_batch_tiered", "load_cold_shard",
    "make_cold_batch_engine",
    "SeriesSource", "random_walk",
    "CompactionPolicy", "CompactionResult", "DeltaShard", "IngestPipeline",
    "MutableIndex", "build_delta_shard",
]
