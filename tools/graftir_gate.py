#!/usr/bin/env python
"""graftir G0 gate: IR contract verification + mutation selftest + the
merged static-analysis SARIF artifact.

Three steps, each failing loudly:

1. ``python -m lambdagap_tpu.analysis --ir`` under ``--max-seconds``
   (default 570): every registered contract verified over the full
   scenario inventory (five learners x four virtual grids, stream
   kernels, three predict engines, linear leaves). The per-program
   verdict cache makes an unchanged-tree re-run a hash walk; the budget
   is enforced on whatever the run actually was, so a broken cache or an
   outgrown inventory fails the gate instead of silently slowing it.
2. ``--ir --selftest``: the seeded-violation mutation suite (extra psum,
   host callback, f64 literal, pre-psum gradient scale, float-fed int
   reduction, unbucketed retrace) must be CAUGHT by the real checkers —
   the suite's teeth are proven on every gate run, not assumed.
3. ``--sarif-out``: render graftlint (warm cache) + graftir (warm cache)
   as SARIF and merge their runs into one artifact for code-scanning
   upload.

Exit 0 only when all requested steps pass.
"""
import argparse
import contextlib
import io
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the gate process itself is lint-side (stdlib-only); the graftir worker
# subprocesses it spawns override this via LAMBDAGAP_IR_CAPTURE
os.environ.setdefault("LAMBDAGAP_LINT_ONLY", "1")

from lambdagap_tpu.analysis import cli  # noqa: E402


def _capture(argv):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    return rc, buf.getvalue()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="graftir_gate")
    ap.add_argument("--max-seconds", type=float, default=570.0,
                    help="wall budget for the IR pass (default 570)")
    ap.add_argument("--sarif-out", default=None, metavar="PATH",
                    help="write the merged graftlint+graftir SARIF here")
    ap.add_argument("--skip-selftest", action="store_true")
    args = ap.parse_args(argv)
    os.chdir(REPO)

    rc = cli.main(["--ir", "--max-seconds", str(args.max_seconds)])
    if rc != 0:
        print("graftir_gate: IR contract verification FAILED (exit "
              f"{rc}) — a lowered program drifted from its declared "
              "contract, or the pass blew its budget", file=sys.stderr)
        return 1

    if not args.skip_selftest:
        rc = cli.main(["--ir", "--selftest"])
        if rc != 0:
            print("graftir_gate: mutation selftest FAILED — a planted "
                  "violation went uncaught; the checkers have lost "
                  "their teeth", file=sys.stderr)
            return 1

    if args.sarif_out:
        rc_l, lint = _capture(["--format", "sarif", "lambdagap_tpu",
                               "tools"])
        rc_i, ir = _capture(["--ir", "--format", "sarif"])
        merged = cli.merge_sarif([lint, ir])
        out_dir = os.path.dirname(args.sarif_out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.sarif_out, "w", encoding="utf-8") as f:
            f.write(merged + "\n")
        print(f"graftir_gate: merged SARIF (graftlint + graftir) -> "
              f"{args.sarif_out}")
        if rc_l != 0 or rc_i != 0:
            # the artifact is still written (it carries the findings),
            # but non-baselined findings keep the gate red
            print(f"graftir_gate: SARIF render saw findings "
                  f"(graftlint rc={rc_l}, graftir rc={rc_i})",
                  file=sys.stderr)
            return 1

    print("graftir_gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
