"""Mean ``rows`` of the engine's ``decode_step`` spans in the window."""
from servebench.metrics.common import spans_in_window


def read(run):
    rows = [s.args.get("rows") for s in spans_in_window(run, "decode_step")]
    rows = [r for r in rows if r is not None]
    return sum(rows) / len(rows) if rows else None
