"""quickwit_tpu — a TPU-native distributed search engine.

A from-scratch reimplementation of the capabilities of quickwit
(https://github.com/quickwit-oss/quickwit): sub-second full-text search and
ES-compatible aggregations over immutable index "splits" stored on object
storage, with decoupled stateless indexers and searchers.

Unlike the Rust/tantivy reference, the leaf-search hot path — term/range
filtering, BM25 scoring, top-K collection, and columnar aggregations — runs
as JAX/XLA kernels over HBM-resident dense arrays, and the
scatter-gather merge tree is a sharded top-K + aggregation reduce over a
`jax.sharding.Mesh` (ICI collectives) instead of per-node gRPC fan-in.

Package layout (mirrors the reference's layer map, SURVEY.md §1):

- ``common``        foundation utilities (reference: quickwit-common)
- ``config``        node/index/source config (reference: quickwit-config)
- ``query``         serializable QueryAst + parsers (reference: quickwit-query)
- ``models``        doc mapping, split/index metadata (quickwit-doc-mapper,
                    quickwit-metastore's SplitMetadata)
- ``storage``       object-storage abstraction + caches (quickwit-storage)
- ``index``         TPU-first split format: blocked postings, columns,
                    doc store, hotcache (quickwit-directories + tantivy fmt)
- ``ops``           JAX kernels: masks, BM25, top-K, aggregations
- ``search``        leaf/root search, collectors, caches (quickwit-search)
- ``parallel``      mesh fan-out + ICI merge tree (the pmap'd merge of
                    BASELINE.json)
- ``indexing``      split building pipeline + merges (quickwit-indexing)
- ``ingest``        WAL-backed ingest, router/ingester (quickwit-ingest)
- ``metastore``     file-backed metastore + publish protocol
- ``cluster``       membership + failure detection (quickwit-cluster)
- ``control_plane`` indexing plan scheduler (quickwit-control-plane)
- ``janitor``       GC + retention (quickwit-janitor)
- ``serve``         REST + ES-compatible APIs (quickwit-serve)
"""

__version__ = "0.1.0"

# i64 timestamp columns (micros since epoch, ~1e15) and f64 aggregation
# accumulators need 64-bit math; f64 is exact for integers < 2^53 which covers
# all datetime micros. Must be set before any tracing.
import jax as _jax

_jax.config.update("jax_enable_x64", True)
