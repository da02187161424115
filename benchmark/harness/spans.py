"""The program's per-request spans, read from its Server-Timing header."""

from __future__ import annotations


def parse_server_timing(header: str | None) -> dict:
    """`"decode;dur=3.68, drain;dur=7.53"` -> {"decode": 3.68, "drain": 7.53}.
    Entries without a duration are skipped; repeated names sum."""
    out: dict = {}
    for entry in (header or "").split(","):
        parts = [p.strip() for p in entry.split(";")]
        name = parts[0]
        if not name:
            continue
        for p in parts[1:]:
            key, _, val = p.partition("=")
            if key.strip() == "dur":
                try:
                    out[name] = out.get(name, 0.0) + float(val.strip().strip('"'))
                except ValueError:
                    pass
    return out
