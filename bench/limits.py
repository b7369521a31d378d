"""Set a cell's limits of ``correct`` from readings (not run by the benchmark).

    python3 bench/limits.py --readings <readings.jsonl> [--write <cell>]

Reads the lines bench/readings.py wrote.  For each compared number:

* lower: the largest reading of the program over its seeds;
* upper: the least of the control's smallest reading over its seeds
  (bfloat16 reference in the program's place), where that is three times
  the lower or more, and of each planted fault's smallest reading, where
  that is ten times the lower or more (a step that keeps its state:
  three times; its readings are the reference's initial weights put in
  the program's place);
* limit: lower^(1/3) * upper^(2/3), between the two with the more room
  above the lower.  A number with no upper reading gets no limit.

Prints one JSON object, with the numbers that catch the control and each
fault; ``--write`` puts the limits into the cell's file.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from check import NUMBERS  # noqa: E402
FAULTS = ("unchanged_state", "half_batch", "no_exchange")


def limits(lines):
    out = {}
    for k in NUMBERS:
        read = [r for r in lines if k in r["program"]]   # older lines may lack a number
        lower = max(r["program"][k] for r in read)
        least = {}   # each candidate's smallest reading over its seeds
        for name in ("control_bf16",) + FAULTS:
            vals = [r[name][k] for r in read if name in r]
            if vals:
                least[name] = min(vals)
        need = {"control_bf16": 3, "unchanged_state": 3}
        uppers = {n: v for n, v in least.items() if v >= need.get(n, 10) * lower}
        upper = min(uppers.values()) if uppers else None
        out[k] = {"lower": lower, "upper": upper, "least": least,
                  "upper_from": sorted(uppers), "seeds": len(read),
                  "control_seeds": sum("control_bf16" in r for r in read),
                  "limit": None if upper is None else lower ** (1 / 3) * upper ** (2 / 3)}
    caught = {}
    for name in ("control_bf16",) + FAULTS:
        caught[name] = sorted(k for k, v in out.items() if v["limit"] is not None
                              and name in v["least"] and v["least"][name] > v["limit"])
    return {"numbers": out, "caught_by": caught}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--readings", required=True)
    ap.add_argument("--write", default="")
    args = ap.parse_args()
    lines = [json.loads(x) for x in Path(args.readings).read_text().splitlines() if x.strip()]
    table = limits(lines)
    print(json.dumps(table))
    if args.write:
        path = BENCH / "workloads" / f"{args.write}.json"
        spec = json.loads(path.read_text())
        spec["limits"] = {k: v["limit"] for k, v in table["numbers"].items()
                          if v["limit"] is not None}
        path.write_text(json.dumps(spec, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
