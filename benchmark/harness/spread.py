"""Medians and spreads of one prove.sh call, as the contract reads them: a
spread is (Q3 - Q1) / median by ``statistics.quantiles(values, n=4)``;
``setup_s`` leaves the first run (it compiles) out and reports it apart."""

import json
import statistics
import sys


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(path):
    runs = [json.loads(line) for line in open(path) if line.strip()]
    ok = [r for r in runs if isinstance(r.get("line"), dict)]
    report = {
        "file": path, "runs": len(runs),
        "correct": sum(bool(r["line"].get("correct")) for r in ok),
        "failed": sum(int(r["line"].get("failed", 0)) for r in ok),
        "memory_peak_bytes": max((r["line"]["device"].get("memory_peak_bytes") or 0 for r in ok), default=0),
    }
    names = sorted({k for r in ok for k in r["line"]["metrics"]})
    for name in names:
        rows = [r for r in ok if name in r["line"]["metrics"]]
        if name == "setup_s":
            report["first_setup_s"] = [r["line"]["metrics"][name]["value"] for r in rows if r["first_run"]]
            rows = [r for r in rows if not r["first_run"]]
        values = [r["line"]["metrics"][name]["value"] for r in rows]
        if values:
            report[name] = {"n": len(values), "median": statistics.median(values),
                            "min": min(values), "max": max(values), "spread": spread(values)}
    print(json.dumps({"spread_report": report}))


if __name__ == "__main__":
    main(sys.argv[1])
