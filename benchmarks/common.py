"""CSV output and a terminal sparkline for the paper-reproduction scripts."""
from __future__ import annotations

import os
from typing import List

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def write_csv(name: str, header: List[str], rows: List[List]) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        f.write(",".join(header) + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return path


def ascii_curve(xs, ys, width: int = 60, label: str = "") -> str:
    """One-line sparkline for quick terminal inspection."""
    if not ys:
        return f"{label}: (no data)"
    lo, hi = min(ys), max(ys)
    span = (hi - lo) or 1.0
    chars = " .:-=+*#%@"
    pts = []
    for i in range(width):
        j = int(i / width * (len(ys) - 1))
        pts.append(chars[int((ys[j] - lo) / span * (len(chars) - 1))])
    return f"{label:24s} [{''.join(pts)}] {lo:.3f}..{hi:.3f}"
