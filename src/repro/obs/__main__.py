"""``python -m repro.obs`` — read traced fits from the command line.

  summarize DIR     one JSON summary of a trace directory (rounds,
                    k-scans, span timings, retraces)
  tail DIR [-n N]   the last N merged events, one JSON line each
  merge DIR [-o F]  merge per-process files into one time-ordered
                    JSONL stream (stdout or -o FILE)

Pure reader: imports no jax, touches no devices — safe on a login node
while the fit is still running (files are line-buffered JSONL; a
partial final line is a loud error only if the writer died mid-line).
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.obs.trace import read_events, summarize, tail_events


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.obs",
        description="summarize / tail / merge repro trace directories")
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser("summarize", help="aggregate a trace directory")
    ps.add_argument("trace_dir")

    pt = sub.add_parser("tail", help="last N merged events")
    pt.add_argument("trace_dir")
    pt.add_argument("-n", type=int, default=20, metavar="N")

    pm = sub.add_parser("merge",
                        help="merged time-ordered JSONL event stream")
    pm.add_argument("trace_dir")
    pm.add_argument("-o", "--out", default=None,
                    help="write to FILE instead of stdout")

    args = p.parse_args(argv)
    try:
        if args.cmd == "summarize":
            print(json.dumps(summarize(read_events(args.trace_dir)),
                             indent=2, sort_keys=True))
        elif args.cmd == "tail":
            for e in tail_events(args.trace_dir, args.n):
                print(json.dumps(e, separators=(",", ":")))
        elif args.cmd == "merge":
            events = read_events(args.trace_dir)
            out = (open(args.out, "w", encoding="utf-8")
                   if args.out else sys.stdout)
            try:
                for e in events:
                    out.write(json.dumps(e, separators=(",", ":")) + "\n")
            finally:
                if args.out:
                    out.close()
    except (FileNotFoundError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
