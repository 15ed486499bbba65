"""What a scan dispatch has to move, computed from its staged shapes.

Kept with the benchmark so that no PR that claims a gain can change it.
A scan reads every staged column of its group once: per entry the kv
key and kv value ids of each slot (at the widths the dictionaries
allow: int8 up to 127 ids, int16 up to 32,767, else int32), start, end
and duration (uint32 each) and the valid flag (one byte): 13 B of entry
columns. A fused dispatch of several queries reads the columns once.
Compares and reductions are not counted: on these narrow lanes the
memory roofline is the lower bound the kernel cannot beat, and the
share says how far above it the kernel runs.
"""

from __future__ import annotations

import json
import os

PAGE_ENTRIES = 1024
ENTRY_COLUMN_BYTES = 13      # start, end, duration: 3 x uint32; valid: 1


def id_width(n_ids: int) -> int:
    return 1 if n_ids <= 127 else 2 if n_ids <= 32_767 else 4


def scan_bytes(pages: int, kv_slots: int, n_keys: int, n_vals: int) -> int:
    """Bytes one scan dispatch over `pages` staged pages must read."""
    per_entry = kv_slots * (id_width(n_keys) + id_width(n_vals)) \
        + ENTRY_COLUMN_BYTES
    return pages * PAGE_ENTRIES * per_entry


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to chipbench/peaks.json with its source")
    return table[device_kind]


def roofline_s(nbytes: float, device_kind: str) -> float:
    """Least seconds the device could take to read `nbytes` from HBM."""
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
