"""Streaming scan, the producer thread's first half: read, decompress and
decode of one row group (`io.scan.decode_s`, sum over count), over the
window's last queries.  With `scan_stage_ms` it makes the producer's
period, which the consumer's wait (`scan_chunk_ms`) mirrors."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    return span_reduce.per_occurrence_ms(ctx, "io.scan.decode")
