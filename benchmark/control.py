"""`python3 -m benchmark.control --workload <name> --seeds a b c`: the
control of a train cell's output check. The plain reference is put in the
program's place and computed in fp8, the nearest precision below the
bfloat16 the configurations state, at the cell's own sizes, and compared
with the float32 reference under the cell's own limits: it has to come
out NOT correct. `--also bf16` reads the stated precision too, which has
to pass. Needs no measured window; the benchmark's own runs never call
this."""

import argparse
import json
import os
import sys

from benchmark import check_train, spec


def main(argv=None, root=spec.REPO_ROOT, out_dir=None):
    ap = argparse.ArgumentParser(prog="benchmark.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--also", nargs="*", default=[])
    args = ap.parse_args(argv)
    _, _, workload, config = spec.load_cell(args.workload, root)
    ref = spec.module("reference", config["reference"])
    traffic = spec.module("traffic", workload["traffic"]["kind"])
    out_dir = out_dir or os.path.join(root, ".bench_out")
    os.makedirs(out_dir, exist_ok=True)
    caught = True
    for seed in args.seeds:
        plain = traffic.make(seed, workload["traffic"],
                             config["model"])["plain"]
        want = check_train.follow_reference(ref, config, seed, plain)
        for precision in ["fp8", *args.also]:
            got = check_train.follow_reference(ref, config, seed, plain,
                                               precision)
            rows = check_train.compare(got, want, workload["limits"],
                                       config.get("check_leaves_left_out"))
            correct = all(ok for *_, ok in rows)
            with open(os.path.join(
                    out_dir, f"control-{args.workload}-{seed}-{precision}"
                    ".json"), "w", encoding="utf-8") as fh:
                json.dump({"seed": seed, **check_train.detail(got, want)},
                          fh)
            print(json.dumps({"seed": seed, "precision": precision,
                              "correct": correct,
                              "rows": {n: v for n, v, *_ in rows}}),
                  flush=True)
            if precision == "fp8":
                caught = caught and not correct
    print("control", "caught" if caught else "NOT caught", flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
