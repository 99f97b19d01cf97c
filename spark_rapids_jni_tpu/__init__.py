"""spark_rapids_jni_tpu — TPU-native re-implementation of spark-rapids-jni.

The reference (`/root/reference`, NVIDIA spark-rapids-jni) is the native acceleration
layer for Spark SQL columnar processing: Java API -> JNI handle-passing -> CUDA
kernels over cudf columns.  This package provides the same capability surface
TPU-first:

- ``columnar``: Arrow-layout columns/tables as sharded jax.Arrays in HBM
  (analog of cudf columns + the cudf Java handle objects).
- ``ops``: the op surface (RowConversion, Hash, CastStrings, ZOrder, BloomFilter,
  TimeZoneDB, RegexRewrite, joins/aggregates) as jit-able XLA programs (analog
  of src/main/cpp/src/*.cu).
- ``parallel``: hash-partition shuffle / exchange as ICI collectives over a
  jax.sharding.Mesh (net-new vs the reference, which defers exchange to Spark).
- ``bridge``: native C++ handle-table + IPC bridge so a JVM-side caller round-trips
  host columns to device without sharing an address space (analog of the JNI shims).
- ``io``: chunked columnar file ingest (analog of the chunked Parquet read path).

Int64/float64 columns are first-class in Spark SQL, so x64 is enabled at import.
"""

import jax as _jax

_jax.config.update("jax_enable_x64", True)

from . import dtypes  # noqa: E402
from .columnar.column import Column  # noqa: E402
from .columnar.table import Table  # noqa: E402

__version__ = "0.1.0"


def build_info() -> dict:
    """Build provenance baked in by ``build/build-info`` (analog of the
    reference's jar properties, build/build-info:27-41); falls back to
    version-only metadata for source checkouts."""
    try:
        from ._build_info import BUILD_INFO
        return dict(BUILD_INFO)
    except ImportError:
        return {"version": __version__, "revision": "unknown",
                "branch": "unknown", "date": "unknown", "user": "unknown",
                "url": "unknown"}
__all__ = ["dtypes", "Column", "Table", "__version__"]
