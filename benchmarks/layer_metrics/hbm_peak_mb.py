"""Device: the most device memory the server's first chip held at once
since it started, as the runtime counts it (`peak_bytes_in_use` of the
OP_METRICS snapshot taken after the window), in MiB.  A streamed
aggregate that keeps every chunk's padded partial until the reader closes
shows here, by the length of the stream.  A runtime that reports no
memory statistics (the CPU's) gives nothing to read."""


def read(ctx):
    memory = ctx["snap_end"].get("device", {}).get("memory") or {}
    peak = memory.get("peak_bytes_in_use")
    return None if peak is None else peak / 2 ** 20
