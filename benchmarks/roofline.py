"""The least the chip must move for a router launch, counted from the real
binding table and the real batch — not from the padded arrays the program
happens to build, so the number reads the same whatever implements the match.

A launch has to read every kernel row once (its pattern words or required
(header, value) pairs, 4 B each, and its destination mask), read each
message's words or pairs, and write each message's destination mask. The
match is integer compares only, a few per byte read, so the memory bound is
the roofline (v5e: 819 GB/s against ~100 T int-op/s on the vector units).
"""

from __future__ import annotations


def kernel_rows(table: dict) -> list:
    """The rows that reach the match kernel, as (cells, queue): wildcard
    topic patterns (exact ones are a host dict), every headers binding."""
    rows = []
    for key, queue, args in table["bindings"]:
        if table["type"] == "topic":
            words = key.split(".")
            if "*" in words or "#" in words:
                rows.append((len([w for w in words if w != "#"]), queue))
        elif table["type"] == "headers":
            rows.append((len([k for k in (args or {}) if k != "x-match"]),
                         queue))
    return rows


def launch_bytes(table: dict, msgs: float, cells_per_msg: float) -> float:
    """Bytes one launch must move for `msgs` messages of `cells_per_msg`
    words (topic) or header pairs (headers) each."""
    rows = kernel_rows(table)
    mask_bytes = 4 * -(-len({q for _, q in rows}) // 32)
    table_bytes = sum(4 * cells + mask_bytes for cells, _ in rows)
    return table_bytes + msgs * (4 * cells_per_msg + mask_bytes)


def least_seconds(n_bytes: float, peak: dict) -> float:
    return n_bytes / peak["hbm_bytes_per_s"]
