"""Run the stress harness and emit a machine-readable artifact.

Usage:  python tests/stress/run_stress.py [out.json] [seconds-per-scenario]
(also: `make stress` at the repo root). Sets SWTPU_STRESS=1 itself — this
is the delivery-loop entry the r4 verdict asked for, so the harness runs
instead of sitting behind a gate nobody sets.
"""

import json
import os
import re
import subprocess
import sys
import time


def main() -> int:
    out_path = sys.argv[1] if len(sys.argv) > 1 else "STRESS.json"
    seconds = sys.argv[2] if len(sys.argv) > 2 else "6"
    env = dict(os.environ, SWTPU_STRESS="1", SWTPU_STRESS_SECONDS=seconds)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/stress", "-s", "-rA",
         "--no-header"],
        capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."))
    wall = round(time.time() - t0, 1)
    text = proc.stdout + proc.stderr
    # the -rA short summary pins verdict and test id on ONE line each,
    # immune to -s output interleaving
    scenarios = [{"name": name, "result": verdict}
                 for verdict, name in re.findall(
                     r"^(PASSED|FAILED|ERROR)\s+tests/stress/\S+?::(\w+)",
                     text, re.M)]
    iters = [int(x) for x in re.findall(r"STRESS-ITERS (\d+)", text)]
    mq = re.search(r"STRESS-MQ total=(\d+) dups=(\d+)", text)
    artifact = {
        "harness": "tests/stress (SWTPU_STRESS=1)",
        "seconds_per_scenario": float(seconds),
        "wall_s": wall,
        "scenarios": scenarios,
        "passed": sum(1 for s in scenarios if s["result"] == "PASSED"),
        "failed": sum(1 for s in scenarios if s["result"] != "PASSED"),
        "total_worker_iterations": sum(iters),
        "iterations_per_scenario": iters,
        "invariant_failures": 0 if proc.returncode == 0 else
        sum(1 for s in scenarios if s["result"] != "PASSED"),
    }
    if mq:
        artifact["mq_churn"] = {"messages": int(mq.group(1)),
                                "duplicates": int(mq.group(2))}
    rc = proc.returncode
    if os.environ.get("SWTPU_LOCKCHECK") == "1":
        # `make race`: utils/locktrack prints its exit report to stderr
        # (nothing when no findings). An ABBA ordering cycle fails the
        # run even if every scenario's assertions passed — a deadlock
        # that didn't fire this time is still a deadlock.
        lk = re.search(r"== (\d+) cycle\(s\), (\d+) long hold\(s\)", text)
        cycles, holds = (int(lk.group(1)), int(lk.group(2))) if lk else (0, 0)
        artifact["lockcheck"] = {"cycles": cycles, "long_holds": holds}
        if cycles:
            rc = rc or 3
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))
    if rc != 0:
        sys.stderr.write(text[-4000:])
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
