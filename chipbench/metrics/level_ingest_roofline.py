"""The per-tenant dyadic ingest's share of its roofline: the least bytes
of the window's blocks (``least_bytes`` of ``chipbench/kinds/quantile``:
the live counters of every layer row a block reaches, read and written
once, and the block itself) over the device time of the session's
compiled ``ingest`` at the chip's HBM bandwidth, in percent; read as
``ingest_roofline`` reads it."""

from chipbench.metrics.ingest_roofline import read  # noqa: F401
