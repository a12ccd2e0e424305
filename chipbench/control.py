#!/usr/bin/env python3
"""A cell's control: ``run.py`` with the plain reference, computed in a
lower precision, put in the program's place where ``correct`` is
decided.

    python3 chipbench/control.py --workload olmo-1b.conversation-closed \
        --seed 7 --seconds 51 --trace 0 --quant fp8

The cell runs as ``run.py`` runs it.  At the comparison, each sampled
request's served tokens are replaced by the tokens that the reference
in ``--quant`` puts first at each position of the same prompt and
tokens (``check.control_gap``), so the result's ``correct`` has to come
out false.  Standard error also gives the sound reading of the same
sample: one run gives both readings a limit is set from.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, refmath, run  # noqa: E402


def main(argv=None, **kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--quant", choices=refmath.QUANT, default=check.CONTROL)
    args, rest = ap.parse_known_args(argv)

    def control(ref, weights, conf, picked):
        for k, v in check.readings(ref, weights, conf, picked).items():
            print(f"sound {k}: {v!r}", file=sys.stderr)
        return check.readings(ref, weights, conf, picked, quant=args.quant)

    return run.main(rest, readings=control, **kw)


if __name__ == "__main__":
    sys.exit(main())
