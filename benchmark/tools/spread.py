"""What the sets of runs say about the bounds (no chip: it reads a file):

    python -m benchmark.tools.spread chiprun_out/sets_<cell>.jsonl [--markdown]

``tools/sets.sh`` writes one line a run, ``{"set", "seed", "line"}``. For
every statistic of the result lines (the cell's ``metrics`` and whatever
else stands under ``end_to_end``), per set: the median, the spread as the
contract reckons it (third quartile less first, ``statistics.quantiles(n=4)``,
over the median) and as ISSUE 33 does (largest less smallest over the median,
without the run farthest from the median where that narrows it). Then the
two numbers a check holds a bound to: ``tight`` = the mean over the sets of
the quartile spread without each set's run farthest from its median, which
may be at most HALF the bound; ``loose`` = the widest quartile spread (of a
set, or of all runs together), of which the bound may be at most EIGHT
times (a bound of 0.01 is never too loose). ``ratio`` = tight / bound.
"""

from __future__ import annotations

import argparse
import json
import statistics
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
#: Judged by whether its median got worse, never by its spread.
MEDIAN_ONLY = ("setup_s",)


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median; 0 for fewer than two values."""
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def without_farthest(values) -> list:
    """``values`` less the one farthest from their median."""
    if len(values) < 3:
        return list(values)
    med = statistics.median(values)
    out = sorted(values, key=lambda v: abs(v - med))
    return out[:-1]


def range_spread(values) -> float:
    """(max - min) / median, without the run farthest from the median
    where that narrows it (it always does, or leaves it as it was)."""
    if len(values) < 2:
        return 0.0
    kept = without_farthest(values)
    return (max(kept) - min(kept)) / abs(statistics.median(values))


def records(path) -> list:
    """The runs of a sets file, one ``{"set", "seed", "line"}`` each."""
    return [json.loads(t) for t in Path(path).read_text().splitlines() if t.strip()]


def read_sets(path) -> dict:
    """``{statistic: {set: [(seed, value), ...]}}`` of a sets file."""
    out: dict = {}
    for rec in records(path):
        line = rec["line"]
        values = {k: v["value"] for k, v in line.get("metrics", {}).items()}
        for k, v in line.get("end_to_end", {}).items():
            values.setdefault(k, v)
        for k, v in values.items():
            out.setdefault(k, {}).setdefault(rec["set"], []).append((rec["seed"], v))
    return out


def summarise(per_set: dict, bound=None) -> dict:
    """One statistic's ``{set: [(seed, value)]}`` to its medians, spreads
    and the two numbers held against ``bound``."""
    sets = {k: [v for _, v in runs] for k, runs in sorted(per_set.items())}
    everything = [v for vs in sets.values() for v in vs]
    tight = statistics.mean(quartile_spread(without_farthest(vs)) for vs in sets.values())
    loose = max([quartile_spread(vs) for vs in sets.values()] + [quartile_spread(everything)])
    row = {
        "medians": {k: statistics.median(vs) for k, vs in sets.items()},
        "quartile_spread": {k: quartile_spread(vs) for k, vs in sets.items()},
        "range_spread": {k: range_spread(vs) for k, vs in sets.items()},
        "tight": tight, "loose": loose, "bound": bound,
    }
    if bound:
        row["ratio"] = tight / bound
        row["too_tight"] = tight > bound / 2
        row["too_loose"] = bound > 0.01 and bound > 8 * loose
    return row


def bounds(root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"]: m["bound"] for m in bench["end_to_end"]}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("file")
    parser.add_argument("--markdown", action="store_true")
    args = parser.parse_args(argv)
    known = bounds()
    stats = read_sets(args.file)
    correct = [rec["line"]["correct"] for rec in records(args.file)]
    print(f"{args.file}: {len(correct)} runs, {sum(correct)} correct")
    if args.markdown:
        print("| statistic | medians (set 1, 2) | quartile spread | range spread | tight | loose | bound | tight ÷ bound |")
        print("| --- | --- | --- | --- | --- | --- | --- | --- |")
    for name, per_set in stats.items():
        row = summarise(per_set, known.get(name))
        cells = [
            name, ", ".join("%.6g" % v for v in row["medians"].values()),
            ", ".join("%.3f %%" % (100 * v) for v in row["quartile_spread"].values()),
            ", ".join("%.3f %%" % (100 * v) for v in row["range_spread"].values()),
            "%.3f %%" % (100 * row["tight"]), "%.3f %%" % (100 * row["loose"]),
            "" if row["bound"] is None else "%g" % row["bound"],
            "" if row["bound"] is None else "%.2f%s" % (
                row["ratio"], " (median only)" if name in MEDIAN_ONLY else
                " TOO TIGHT" if row["too_tight"] else
                " too loose" if row["too_loose"] else ""),
        ]
        print(("| " + " | ".join(cells) + " |") if args.markdown else "  ".join(cells))


if __name__ == "__main__":
    main()
