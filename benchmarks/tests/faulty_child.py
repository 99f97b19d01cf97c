#!/usr/bin/env python3
"""The benchmark's launcher with the timed path broken underneath: what
`tests/test_faults.py` hands to `run.run_cell` in place of
`server_child.py`, to see `correct` come out false.

    --fault altered_answer   one value of every result moved by one ulp
                             where the executor hands the result over
    --fault half_batch       every streamed chunk loses the second half of
                             its rows before it reaches the device
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import server_child  # noqa: E402  (benchmarks/server_child.py)


def altered_answer() -> None:
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar.column import Column
    from spark_rapids_jni_tpu.columnar.table import Table
    from spark_rapids_jni_tpu.dtypes import FLOAT64
    from spark_rapids_jni_tpu.engine.cache import CompiledPlan
    inner = CompiledPlan.execute

    def execute(self, *args, **kwargs):
        out = inner(self, *args, **kwargs)
        cols = list(out.columns)
        for i, c in enumerate(cols):
            if c.dtype.id == FLOAT64.id and c.size:
                data = jnp.asarray(c.data)
                data = data.at[0].set(jnp.nextafter(data[0], jnp.inf))
                cols[i] = Column(c.dtype, data=data, validity=c.validity)
                break
        return Table(cols, out.names)

    CompiledPlan.execute = execute


def half_batch() -> None:
    from spark_rapids_jni_tpu.io.parquet import ParquetChunkedReader
    inner = ParquetChunkedReader._host_slices_group

    def _host_slices_group(self, gi):
        for sl in inner(self, gi):
            keep = sl[0].num_rows // 2
            yield [h.slice(0, keep) for h in sl]

    ParquetChunkedReader._host_slices_group = _host_slices_group


FAULTS = {"altered_answer": altered_answer, "half_batch": half_batch}

if __name__ == "__main__":
    i = sys.argv.index("--fault")
    fault = sys.argv[i + 1]
    del sys.argv[i:i + 2]
    FAULTS[fault]()
    server_child.main()
