"""Logical type system for the TPU-native columnar engine.

Mirrors the (type-id, scale) pair that crosses the reference's FFI boundary
(`make_data_type(jni_type_id, scale)` — reference RowConversionJni.cpp:58-61) and the
cudf ``data_type`` the kernels consume (reference row_conversion.hpp:27-36).  The
integer values follow cudf's ``type_id`` enum so serialized schemas stay
wire-compatible with the Java layer's ``DType.getTypeId().getNativeId()``.

Decimals are represented as scaled integers (DECIMAL32 -> int32 backing,
DECIMAL64 -> int64 backing) with a *negative* scale meaning the stored integer is
``value * 10**(-scale)`` — identical to cudf fixed_point semantics exercised by the
reference round-trip test (RowConversionTest.java:37-38, decimal32 scale -3 /
decimal64 scale -8).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np


class TypeId(enum.IntEnum):
    """cudf-compatible type ids (subset we implement + nested ids we recognise)."""

    EMPTY = 0
    INT8 = 1
    INT16 = 2
    INT32 = 3
    INT64 = 4
    UINT8 = 5
    UINT16 = 6
    UINT32 = 7
    UINT64 = 8
    FLOAT32 = 9
    FLOAT64 = 10
    BOOL8 = 11
    TIMESTAMP_DAYS = 12
    TIMESTAMP_SECONDS = 13
    TIMESTAMP_MILLISECONDS = 14
    TIMESTAMP_MICROSECONDS = 15
    TIMESTAMP_NANOSECONDS = 16
    DURATION_DAYS = 17
    DURATION_SECONDS = 18
    DURATION_MILLISECONDS = 19
    DURATION_MICROSECONDS = 20
    DURATION_NANOSECONDS = 21
    DICTIONARY32 = 22
    STRING = 23
    LIST = 24
    DECIMAL32 = 25
    DECIMAL64 = 26
    DECIMAL128 = 27
    STRUCT = 28


# Physical (storage) jnp dtype per type id, for the fixed-width types.
_STORAGE: dict[TypeId, np.dtype] = {
    TypeId.INT8: np.dtype(np.int8),
    TypeId.INT16: np.dtype(np.int16),
    TypeId.INT32: np.dtype(np.int32),
    TypeId.INT64: np.dtype(np.int64),
    TypeId.UINT8: np.dtype(np.uint8),
    TypeId.UINT16: np.dtype(np.uint16),
    TypeId.UINT32: np.dtype(np.uint32),
    TypeId.UINT64: np.dtype(np.uint64),
    TypeId.FLOAT32: np.dtype(np.float32),
    TypeId.FLOAT64: np.dtype(np.float64),
    TypeId.BOOL8: np.dtype(np.uint8),  # 1-byte bool, cudf BOOL8 storage
    TypeId.TIMESTAMP_DAYS: np.dtype(np.int32),
    TypeId.TIMESTAMP_SECONDS: np.dtype(np.int64),
    TypeId.TIMESTAMP_MILLISECONDS: np.dtype(np.int64),
    TypeId.TIMESTAMP_MICROSECONDS: np.dtype(np.int64),
    TypeId.TIMESTAMP_NANOSECONDS: np.dtype(np.int64),
    TypeId.DURATION_DAYS: np.dtype(np.int32),
    TypeId.DURATION_SECONDS: np.dtype(np.int64),
    TypeId.DURATION_MILLISECONDS: np.dtype(np.int64),
    TypeId.DURATION_MICROSECONDS: np.dtype(np.int64),
    TypeId.DURATION_NANOSECONDS: np.dtype(np.int64),
    TypeId.DECIMAL32: np.dtype(np.int32),
    TypeId.DECIMAL64: np.dtype(np.int64),
    # 128-bit decimals: two little-endian 64-bit limbs (lo unsigned, hi
    # signed two's complement) — byte-identical to cudf's __int128 storage.
    # Device buffers hold the limbs as int64[n, 2] (no int128 in XLA).
    TypeId.DECIMAL128: np.dtype([("lo", "<u8"), ("hi", "<i8")]),
}

_NUMERIC_IDS = {
    TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.INT64,
    TypeId.UINT8, TypeId.UINT16, TypeId.UINT32, TypeId.UINT64,
    TypeId.FLOAT32, TypeId.FLOAT64,
}


@dataclass(frozen=True)
class DType:
    """Logical column type: (type-id, decimal scale).

    Matches the int pair the reference marshals per column across JNI
    (RowConversion.java:113-118 flattens schema to parallel typeId/scale arrays).
    ``precision`` is a decimal's SQL precision where it is known (a Parquet
    footer's, or Spark's rule for a computed column; 0 = unknown): metadata
    only, outside equality and hashing — the storage is the type-id's.
    """

    id: TypeId
    scale: int = 0
    precision: int = field(default=0, compare=False, repr=False)

    def __post_init__(self):
        if self.scale != 0 and not self.is_decimal:
            raise ValueError(f"non-zero scale on non-decimal type {self.id!r}")

    # -- classification ----------------------------------------------------
    @property
    def is_fixed_width(self) -> bool:
        return self.id in _STORAGE

    @property
    def is_decimal(self) -> bool:
        return self.id in (TypeId.DECIMAL32, TypeId.DECIMAL64, TypeId.DECIMAL128)

    @property
    def is_numeric(self) -> bool:
        return self.id in _NUMERIC_IDS

    @property
    def is_integral(self) -> bool:
        return self.id in _NUMERIC_IDS and self.id not in (TypeId.FLOAT32, TypeId.FLOAT64)

    @property
    def is_floating(self) -> bool:
        return self.id in (TypeId.FLOAT32, TypeId.FLOAT64)

    @property
    def is_timestamp(self) -> bool:
        return TypeId.TIMESTAMP_DAYS <= self.id <= TypeId.TIMESTAMP_NANOSECONDS

    @property
    def is_string(self) -> bool:
        return self.id == TypeId.STRING

    @property
    def is_nested(self) -> bool:
        return self.id in (TypeId.LIST, TypeId.STRUCT)

    # -- physical layout ---------------------------------------------------
    @property
    def storage(self) -> np.dtype:
        """numpy/jnp storage dtype of the data buffer (fixed-width types only)."""
        try:
            return _STORAGE[self.id]
        except KeyError:
            raise TypeError(f"{self.id!r} has no fixed-width storage dtype") from None

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.storage)

    @property
    def device_storage(self) -> np.dtype:
        """dtype of the on-device data buffer.

        FLOAT64 columns store IEEE-754 *bit patterns* as int64: TPUs have no
        f64 ALU and XLA's emulation holds f64 in an f32 pair, which cannot even
        represent every double (verified on v5e: np.pi corrupts at transfer,
        1e300 -> inf).  Integer storage is exact, so the data plane (row
        conversion, hashing, sorting, shuffles) stays bit-perfect; float
        *arithmetic* materializes the hardware approximation via
        ``Column.float_values()``.
        """
        if self.id == TypeId.FLOAT64:
            return np.dtype(np.int64)
        if self.id == TypeId.DECIMAL128:
            return np.dtype(np.int64)  # as int64[n, 2] limb pairs
        return self.storage

    @property
    def itemsize(self) -> int:
        """Bytes per element in the packed row wire format.

        Matches ``cudf::size_of`` as used by the reference layout planner
        (row_conversion.cu:437 ``size_per_row = ... size_of(col.type())``).
        """
        return self.storage.itemsize

    def __repr__(self):
        if self.is_decimal:
            return f"DType({self.id.name}, scale={self.scale})"
        return f"DType({self.id.name})"


# Convenience singletons, mirroring ai.rapids.cudf.DType statics used by the
# reference tests (RowConversionTest.java:30-39).
INT8 = DType(TypeId.INT8)
INT16 = DType(TypeId.INT16)
INT32 = DType(TypeId.INT32)
INT64 = DType(TypeId.INT64)
UINT8 = DType(TypeId.UINT8)
UINT16 = DType(TypeId.UINT16)
UINT32 = DType(TypeId.UINT32)
UINT64 = DType(TypeId.UINT64)
FLOAT32 = DType(TypeId.FLOAT32)
FLOAT64 = DType(TypeId.FLOAT64)
BOOL8 = DType(TypeId.BOOL8)
STRING = DType(TypeId.STRING)
TIMESTAMP_DAYS = DType(TypeId.TIMESTAMP_DAYS)
TIMESTAMP_SECONDS = DType(TypeId.TIMESTAMP_SECONDS)
TIMESTAMP_MILLISECONDS = DType(TypeId.TIMESTAMP_MILLISECONDS)
TIMESTAMP_MICROSECONDS = DType(TypeId.TIMESTAMP_MICROSECONDS)
TIMESTAMP_NANOSECONDS = DType(TypeId.TIMESTAMP_NANOSECONDS)


def decimal32(scale: int, precision: int = 0) -> DType:
    return DType(TypeId.DECIMAL32, scale, precision)


def decimal64(scale: int, precision: int = 0) -> DType:
    return DType(TypeId.DECIMAL64, scale, precision)


def decimal128(scale: int) -> DType:
    return DType(TypeId.DECIMAL128, scale)


LIST = DType(TypeId.LIST)
STRUCT = DType(TypeId.STRUCT)


def from_numpy_dtype(np_dtype) -> DType:
    """Map a numpy dtype to the engine DType (bool -> BOOL8, datetime64 -> timestamp)."""
    np_dtype = np.dtype(np_dtype)
    if np_dtype == np.bool_:
        return BOOL8
    if np_dtype.kind == "M":  # datetime64
        unit = np.datetime_data(np_dtype)[0]
        return {
            "D": TIMESTAMP_DAYS,
            "s": TIMESTAMP_SECONDS,
            "ms": TIMESTAMP_MILLISECONDS,
            "us": TIMESTAMP_MICROSECONDS,
            "ns": TIMESTAMP_NANOSECONDS,
        }[unit]
    for tid, storage in _STORAGE.items():
        if storage == np_dtype and tid not in (
            TypeId.BOOL8, TypeId.DECIMAL32, TypeId.DECIMAL64,
            TypeId.DECIMAL128,
        ) and not (TypeId.TIMESTAMP_DAYS <= tid <= TypeId.DURATION_NANOSECONDS):
            return DType(tid)
    raise TypeError(f"unsupported numpy dtype {np_dtype}")
