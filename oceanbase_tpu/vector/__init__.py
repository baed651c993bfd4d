"""Columnar vector formats for TPU HBM.

Reference analog: the "vec 2.0 rich format" (src/share/vector — ObIVector,
src/share/vector/type_traits.h:16-25).  The reference needs five physical
layouts because CPU operators want pointer/length arrays; on TPU all layouts
collapse to dense SoA device arrays:

- VEC_FIXED          -> one dense jax array per column
- VEC_DISCRETE /
  VEC_CONTINUOUS     -> dictionary codes (int32) + host-side value dictionary
- VEC_UNIFORM(_CONST)-> scalar broadcast at trace time
- null bitmap        -> a bool validity array per column
- ObBatchRows.skip_  -> a bool row-mask per relation (True = row is live)
"""

from oceanbase_tpu.vector.column import (
    Column,
    Relation,
    StringDict,
    bucket_capacity,
    empty_relation,
    from_numpy,
    prefetch,
    to_numpy,
)

__all__ = [
    "Column",
    "Relation",
    "StringDict",
    "bucket_capacity",
    "empty_relation",
    "from_numpy",
    "prefetch",
    "to_numpy",
]
