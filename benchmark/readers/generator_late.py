"""How late the generator ran: 95th percentile over all rows of (on the
wire - due), from the generator process's own report. A starved generator
shows here, not as a fast server."""


def read(record: dict, definition: dict):
    report = record.get("generator")
    return None if report is None else report["late_ms_p95"]
