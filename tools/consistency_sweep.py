"""TPU-vs-CPU same-suite consistency sweep (SURVEY §4: the reference's
strongest oracle — rerun the unit suite on the accelerator;
tests/python/gpu/test_operator_gpu.py pattern).

Runs the operator-oracle and model test files on the REAL chip
(MXTPU_TEST_PLATFORM=tpu: conftest skips the CPU retarget, pins f32
matmul precision to "highest", and applies the reference
check_consistency accelerator tolerance floor rtol 1e-3 / atol 1e-5),
then writes docs/consistency_tpu.md with per-file results and the
failure triage.

Usage: python tools/consistency_sweep.py [--quick]
(one process at a time — a chip belongs to one process)
"""
import argparse
import datetime
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Single-device operator/model files. Mesh-based suites (test_parallel,
# test_moe, test_dist_multiprocess, test_sharded_checkpoint) need 8
# devices and stay on the virtual CPU mesh.
FILES = [
    "test_operator.py", "test_operator_oracle.py",
    "test_operator_reference_port.py", "test_operator_reference_port2.py",
    "test_operator_dtypes.py", "test_operator_extra.py",
    "test_operator_math_extra.py", "test_loss_oracle.py",
    "test_ste_and_pdf_ops.py", "test_ndarray.py", "test_autograd.py",
    "test_numpy.py", "test_gluon.py", "test_rnn.py",
    "test_transformer_ops.py", "test_spatial_ops.py",
    "test_detection_ops.py", "test_proposal_ops.py",
    "test_quantized_ops.py", "test_random_stats.py",
]
QUICK = ["test_operator_oracle.py", "test_operator_dtypes.py",
         "test_loss_oracle.py", "test_gluon.py"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    files = QUICK if args.quick else FILES

    env = dict(os.environ, MXTPU_TEST_PLATFORM="tpu",
               MXTPU_TEST_ALLCLOSE_FLOOR="1")
    rows = []
    failures = []
    t_all = time.time()
    for f in files:
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, "-m", "pytest", os.path.join("tests", f),
             "-q", "--no-header", "-p", "no:cacheprovider"],
            capture_output=True, text=True, cwd=ROOT, env=env,
            timeout=3600)
        dt = time.time() - t0
        tail = (r.stdout or "").strip().splitlines()
        summary = tail[-1] if tail else "(no output)"
        m = re.search(r"(\d+) passed", summary)
        n_pass = int(m.group(1)) if m else 0
        m = re.search(r"(\d+) failed", summary)
        n_fail = int(m.group(1)) if m else 0
        m = re.search(r"(\d+) skipped", summary)
        n_skip = int(m.group(1)) if m else 0
        # A collection error or crash matches neither regex; don't let it
        # masquerade as a green run — count it as one failure with context.
        if r.returncode != 0 and n_fail == 0:
            n_fail = 1
            err_tail = ((r.stderr or "") + "\n" + (r.stdout or ""))
            err_tail = " / ".join(err_tail.strip().splitlines()[-3:])
            failures.append("CRASH %s (rc=%d): %s" % (f, r.returncode,
                                                      err_tail[:400]))
        rows.append((f, n_pass, n_fail, n_skip, dt))
        print("%-32s %3d passed %3d failed %3d skipped  %5.1fs"
              % (f, n_pass, n_fail, n_skip, dt), flush=True)
        if n_fail:
            for line in (r.stdout or "").splitlines():
                if line.startswith("FAILED"):
                    failures.append(line.strip())
    total = time.time() - t_all

    tp = sum(r[1] for r in rows)
    tf = sum(r[2] for r in rows)
    ts = sum(r[3] for r in rows)
    out = os.path.join(ROOT, "docs", "consistency_tpu.md")
    with open(out, "w") as fh:
        fh.write("# TPU-vs-CPU consistency sweep\n\n")
        fh.write("Date: %s. Same suite the CPU mesh runs, retargeted to "
                 "the real chip via `MXTPU_TEST_PLATFORM=tpu` "
                 "(tests/conftest.py), f32 matmul precision `highest`, "
                 "accelerator tolerance floor rtol 1e-3 / atol 1e-5 "
                 "(reference check_consistency GPU-fp32 convention).\n\n"
                 % datetime.date.today().isoformat())
        fh.write("**%d passed / %d failed / %d skipped in %.0fs**\n\n"
                 % (tp, tf, ts, total))
        fh.write("| file | passed | failed | skipped | time |\n")
        fh.write("|---|---|---|---|---|\n")
        for f, p, fl, sk, dt in rows:
            fh.write("| %s | %d | %d | %d | %.1fs |\n" % (f, p, fl, sk, dt))
        if failures:
            fh.write("\n## Failures\n\n")
            for line in failures:
                fh.write("- `%s`\n" % line)
        fh.write("\nRun: `python tools/consistency_sweep.py`\n")
    print("wrote %s: %d passed %d failed %d skipped (%.0fs)"
          % (out, tp, tf, ts, total))
    return 1 if tf else 0


if __name__ == "__main__":
    sys.exit(main())
