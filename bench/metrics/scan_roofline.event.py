"""The device scan's share of its bandwidth roofline (bandwidth-bound).

Bytes are the scan's real, unpadded elements times 24: a float64 ``u``
and ``v`` read and a float64 wait written per element. A placement's
scan has one element per message-hop, so the elements are the window's
message-hops. The least time is bytes over the chip's HBM bandwidth;
the share is that over the device time of the scan programs in the
trace. The count is the same whatever backend runs the scan, so a
narrower or fused kernel cannot read above 100%."""

BYTES_PER_ELEMENT = 24


def read(run):
    if run.trace is None or run.trace.scan_s <= 0 or not run.hops:
        return None
    least_s = run.hops * BYTES_PER_ELEMENT / run.peaks["hbm_bytes_per_s"]
    return least_s / run.trace.scan_s * 100.0
