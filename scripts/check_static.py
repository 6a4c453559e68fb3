"""Static-analysis gate: IR contracts + repo lints + protocol analysis.

One driver for the three layers of ``repro.analysis`` plus (when the
binary exists) ruff with the repo's pinned ``pyproject.toml`` rule set:

* **IR contracts** — compiles every constructible
  strategy × fan-out × wire × fused × faulted round configuration at tiny
  shapes in a forced-8-device child (``repro.launch.mesh.multidev_env``)
  and checks the five ``repro.analysis.contracts`` rules against the
  optimized HLO.
* **Repo lint** — the four AST rules of ``repro.analysis.lint`` over
  ``src/``.
* **Protocol** — the ``MSG_*`` transition-table rules and the
  shared-state locking rules of ``repro.analysis.protocol``.
* **ruff** — style/correctness lints pinned in ``pyproject.toml``; the
  CI image may not ship ruff, in which case the stanza records
  ``available: false`` and the layer is skipped (never silently green:
  the artifact says so).

The full run writes ``experiments/results/static.json``; every run exits 1
on any violation.

    python scripts/check_static.py            # full gate (~2 min)
    python scripts/check_static.py --skip-ir  # AST layers only (seconds)
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
from typing import Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, REPO)

IR_CHILD_TIMEOUT_S = 1200


def run_ir_layer() -> Dict:
    """The contract matrix needs >=4 XLA devices before jax initializes,
    so it runs in a child under the shared forced-8-device recipe."""
    from repro.launch.mesh import multidev_env
    p = subprocess.run([sys.executable, "-m", "repro.analysis.ir"],
                       env=multidev_env(), cwd=REPO, capture_output=True,
                       text=True, timeout=IR_CHILD_TIMEOUT_S)
    if p.returncode != 0:
        sys.stderr.write(p.stdout + p.stderr)
        raise RuntimeError(f"IR contract child failed (exit {p.returncode})")
    return json.loads(p.stdout)


def run_ruff_layer() -> Dict:
    """ruff with the pyproject.toml pins — gated on the binary existing
    (the CI image does not bake it in; nothing may be pip-installed)."""
    exe = shutil.which("ruff")
    if exe is None:
        return {"available": False, "violations": []}
    p = subprocess.run(
        [exe, "check", "--output-format", "concise",
         "src", "scripts", "benchmarks", "tests"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    lines = [ln for ln in p.stdout.splitlines()
             if ln.strip() and not ln.startswith(("Found", "All checks"))]
    return {"available": True, "exit": p.returncode,
            "violations": lines if p.returncode != 0 else []}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-ir", action="store_true",
                    help="skip the compile-time contract matrix (the AST "
                         "layers run in seconds; the artifact is NOT "
                         "emitted without the IR layer)")
    args = ap.parse_args(argv)

    from repro.analysis import lint, protocol

    report: Dict = {}
    if not args.skip_ir:
        print("== IR contracts: compiling the round matrix "
              "(forced 8-device child) ==")
        report["ir"] = run_ir_layer()
        ir = report["ir"]
        print(f"  {ir['configs_evaluated']} configs, "
              f"{ir['rules_evaluated']} rule evaluations, "
              f"{ir['violations']} violation(s)")
        for cname, c in ir["contracts"].items():
            mark = "PASS" if not c["violations"] else "FAIL"
            print(f"  [{mark}] {cname}: {c['evaluated']} evaluated")
            for v in c["violations"]:
                print(f"      - {v}")

    print("== Repo lint (AST over src/) ==")
    report["lint"] = lint.run_lint()
    for rname, r in report["lint"]["rules"].items():
        mark = "PASS" if not r["violations"] else "FAIL"
        print(f"  [{mark}] {rname}: {r['evaluated']} evaluated")
        for v in r["violations"]:
            print(f"      - {v}")

    print("== Protocol analysis (transport/worker) ==")
    report["protocol"] = protocol.run_protocol()
    for rname, r in report["protocol"]["rules"].items():
        mark = "PASS" if not r["violations"] else "FAIL"
        print(f"  [{mark}] {rname}: {r['evaluated']} evaluated")
        for v in r["violations"]:
            print(f"      - {v}")

    print("== ruff (pyproject.toml pins) ==")
    report["ruff"] = run_ruff_layer()
    if not report["ruff"]["available"]:
        print("  ruff not installed in this environment — layer skipped "
              "(recorded in the artifact)")
    else:
        mark = "PASS" if not report["ruff"]["violations"] else "FAIL"
        print(f"  [{mark}] exit {report['ruff']['exit']}")
        for v in report["ruff"]["violations"][:50]:
            print(f"      - {v}")

    layers = [k for k in ("ir", "lint", "protocol") if k in report]
    report["rules_evaluated"] = sum(report[k]["rules_evaluated"]
                                    for k in layers)
    report["violations"] = (sum(report[k]["violations"] for k in layers)
                            + len(report["ruff"]["violations"]))
    report["configs_evaluated"] = (report["ir"]["configs_evaluated"]
                                   if "ir" in report else 0)
    report["pass"] = report["violations"] == 0

    if args.skip_ir:
        # a partial run never overwrites the record of the full gate
        print(f"\ncheck_static (partial, --skip-ir): "
              f"{report['rules_evaluated']} rules, "
              f"{report['violations']} violation(s); artifact not written")
    else:
        out_dir = os.path.join(REPO, "experiments", "results")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "static.json"), "w") as f:
            json.dump(report, f, indent=2)
        print(f"\ncheck_static: {report['configs_evaluated']} IR configs, "
              f"{report['rules_evaluated']} rule evaluations, "
              f"{report['violations']} violation(s) -> "
              f"experiments/results/static.json")
    return 0 if report["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
