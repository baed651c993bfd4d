"""oceanbase_tpu — a TPU-native distributed HTAP SQL database framework.

A from-scratch re-design of OceanBase's capabilities (reference:
/root/reference, see SURVEY.md) with the execution plane on TPU:

- ``vector/``   columnar batch formats in HBM (analog of src/share/vector)
- ``expr/``     expression IR + JAX compiler (analog of src/sql/engine/expr)
- ``exec/``     vectorized physical operators (analog of src/sql/engine)
- ``px/``       parallel execution over a device mesh (analog of src/sql/engine/px + src/sql/dtl)
- ``sql/``      parser / resolver / rewrite / optimizer / code generator / plan cache
                (analog of src/sql/{parser,resolver,rewrite,optimizer,code_generator,plan_cache})
- ``storage/``  LSM-lite column store + memtable (analog of src/storage)
- ``tx/``       MVCC transactions, GTS, 2PC (analog of src/storage/tx)
- ``palf/``     replicated log + election (analog of src/logservice/palf)
- ``server/``   sessions, tenants, config, observability (analog of src/observer)

Control plane runs on host; the compute plane (scan/filter/agg/join/exchange)
is JAX/XLA over TPU with mesh collectives for the PX exchange.
"""

import os as _os

import jax

# The engine computes on exact 64-bit integers (decimals are scaled int64,
# reference: ObNumber / VEC_TC_DEC_INT* in src/share/vector/ob_vector_define.h:47-51).
# TPU emulates i64 with i32 pairs; correctness first, Pallas split kernels later.
jax.config.update("jax_enable_x64", True)

# Persistent compile cache: every plan program otherwise compiles again in
# every process.  JAX_COMPILATION_CACHE_DIR wins when set; otherwise a fixed
# path in the checkout (the path is part of the cache key, so it never moves).
# A process pinned to the CPU keeps none: XLA:CPU's loader logs an error of
# several KB on every hit, and CPU entries are of no use to the chip.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
        (jax.config.jax_platforms or "").lower() != "cpu":
    jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
# keep the small plan programs too
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

__version__ = "0.1.0"
