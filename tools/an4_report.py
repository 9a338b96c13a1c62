"""Fold an lstman4 train.log into profiles/an4_wer_trajectory.json (VERDICT
r4 #4: the real-audio WER trajectory must MOVE, not sit at 1.0).

Parses the trainer's per-epoch eval lines (loss + WER), summarizes the
trajectory, and writes it as one section of the artifact (created when it
does not exist yet). The memorization run
evaluates the TRAIN split (data/an4_memcheck's val manifest lists the 45
real train utterances), so falling WER validates the full
spectrogram -> CTC -> greedy decode -> WER path end to end on real
speech; a separate held-out number on the 8-utterance real val split can
be appended with --val-wer once measured offline.

Usage:
  python tools/an4_report.py --log logs/.../train.log \
      --label "cpu memorization run" [--save]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "profiles", "an4_wer_trajectory.json",
)

# loss may be nan/inf on a diverged run, negative or scientific-notation on
# exotic configs — every such epoch must appear in the audit trajectory,
# not silently vanish because the number's spelling fell outside the
# pattern (ADVICE r5 #4)
_NUM = r"-?(?:[\d.]+(?:e-?\d+)?|nan|inf)"
_EVAL = re.compile(
    rf"epoch (\d+) eval: loss ({_NUM}), count {_NUM}, "
    rf"wer ({_NUM})"
)


def parse_log(path: str) -> list[dict]:
    rows = []
    with open(path) as f:
        for line in f:
            m = _EVAL.search(line)
            if m:
                rows.append(
                    {
                        "epoch": int(m.group(1)),
                        "eval_loss": float(m.group(2)),
                        "wer": float(m.group(3)),
                    }
                )
    return rows


def summarize(rows: list[dict], stride: int = 10) -> dict:
    import math

    if not rows:
        raise SystemExit("no eval lines found in log")
    finite = [r for r in rows if math.isfinite(r["wer"])]
    if not finite:
        raise SystemExit("every eval row is non-finite (diverged run)")
    best = min(finite, key=lambda r: r["wer"])
    # thin the trajectory for the artifact (every `stride` epochs + first,
    # best and last; stride <= 0 keeps all) so the JSON stays reviewable
    keep = {0, rows[-1]["epoch"], best["epoch"]}
    keep.update(
        r["epoch"] for r in rows if stride <= 0 or r["epoch"] % stride == 0
    )
    return {
        # named for what the log proves: epochs whose EVAL line appears
        # (with eval-every-N configs this is not the trained-epoch count)
        "last_eval_epoch": rows[-1]["epoch"],
        "evals": len(rows),
        "diverged_evals": len(rows) - len(finite),
        "best_wer": best["wer"],
        "best_wer_epoch": best["epoch"],
        "final_wer": rows[-1]["wer"],
        "wer_below_1.0": best["wer"] < 1.0,
        "trajectory": [r for r in rows if r["epoch"] in keep],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--log", required=True)
    ap.add_argument("--label", required=True,
                    help="run description recorded in the artifact, e.g. "
                         "'cpu memorization run, lr 1e-3'")
    ap.add_argument("--key", default="memorization_run",
                    help="artifact section to write")
    ap.add_argument("--val-wer", type=float, default=None,
                    help="held-out real-val WER measured offline")
    ap.add_argument("--stride", type=int, default=10)
    ap.add_argument("--save", action="store_true",
                    help="write into the artifact (default: print only)")
    args = ap.parse_args(argv)

    rows = parse_log(args.log)
    section = {
        "label": args.label,
        "log": os.path.relpath(args.log, os.path.dirname(ARTIFACT) + "/.."),
        **summarize(rows, stride=args.stride),
    }
    if args.val_wer is not None:
        section["held_out_val_wer"] = args.val_wer
        section["held_out_caveat"] = (
            "real val split is only 8 utterances (archive tail lost); "
            "the memorization number is the mechanism check, this one is "
            "directional"
        )
    print(json.dumps(section, indent=2))
    if args.save:
        art = json.load(open(ARTIFACT)) if os.path.exists(ARTIFACT) else {}
        art[args.key] = section
        with open(ARTIFACT, "w") as f:
            json.dump(art, f, indent=1)
        print(f"wrote {ARTIFACT}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
