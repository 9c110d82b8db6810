"""Device ms a training step spends under the Mamba-2 mixer's ``ssd``
named scope (the chunked SSD: its forward, its recompute under the
layer's remat and its backward), over the traced period.  The hybrid
driver joins each device op with its phase program's compiled HLO and
writes the sums to ``record["scope_ms"]``; none there, no reading."""


def read(rec):
    return (rec["record"].get("scope_ms") or {}).get("ssd")
