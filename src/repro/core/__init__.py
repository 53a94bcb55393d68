"""AutoGNN core: hardware-driven GNN preprocessing, reimplemented for TPU.

The paper's contribution as composable JAX modules:

* set_partition — UPE primitive (prefix-sum + relocation)
* set_count     — SCR primitive (compare + adder/filter tree)
* ordering      — edge Ordering (chunked radix sort + parallel merge)
* reshaping     — data Reshaping (CSC pointer array via set-counting)
* sampling      — uni-random Selecting (Floyd / keysort / reservoir)
* reindexing    — subgraph Reindexing (sort-unique-rank, no hash map)
* delta         — incremental conversion (O(delta) CSC splice-updates)
* pipeline      — the end-to-end jitted workflow (paper Fig. 14)
* costmodel     — Table-I analytic model + configuration library
* reconfig      — AutoPre / StatPre / DynPre execution modes
"""
from .delta import (DELTA_RANK_PASSES, EdgeDelta, delta_merge, rebuild_coo,
                    reconstruct_sorted_dst)
from .graph import COO, CSC, SENTINEL, Subgraph, next_pow2, pad_to, random_coo
from .set_partition import (displacement, gather_sources_from_counts,
                            partition_indices, radix_partition,
                            radix_sort_by_key, radix_sort_keys,
                            rank_gather_sources, set_partition,
                            tiled_digit_sources)
from .set_count import (count_equal, count_less_than, filter_lookup,
                        rank_in_sorted, rank_in_sorted2,
                        searchsorted_oracle)
from .ordering import (DEFAULT_CHUNK, edge_ordering, edge_ordering_xla,
                       global_radix_sort_by_key, merge_round_fan_ins,
                       merge_sorted, merge_sorted_k, stable_sort_by_key,
                       supports_packed_keys, xla_lex_sort,
                       xla_stable_sort_by_key)
from .reshaping import (build_pointer_array, build_pointer_array_serial,
                        data_reshaping, graph_convert)
from .sampling import sample_khop, select_floyd, select_keysort, \
    select_reservoir
from .reindexing import (ReindexMap, build_reindex_map, reindex_edges,
                         reindex_serial_oracle, reindex_supports_packed)
from .pipeline import (apply_delta, convert, convert_xla, gather_features,
                       preprocess, preprocess_xla_baseline, sample_subgraph)
from .costmodel import (Calibration, EngineConfig, Workload, best_config,
                        bitstream_library, choose_config,
                        delta_epilogue_strategy, delta_merge_seconds,
                        delta_rebuild_seconds, delta_sort_op_count,
                        delta_while_count, delta_workload, estimate_seconds,
                        merge_round_count, pointer_reindex_strategy,
                        relocation_bytes, resolve_delta_mode,
                        resolve_delta_sort_strategy,
                        resolve_reindex_strategy, resolve_sort_strategy)
from .reconfig import DynPre, Engine, autopre, statpre

__all__ = [k for k in dir() if not k.startswith("_")]
