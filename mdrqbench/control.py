"""The control: the reference in bfloat16, put where the program would be.

    python3 mdrqbench/control.py --workload <name> --seconds <s> --seeds 1,2,3

bfloat16 is the precision below the float32 the configurations state, the
step a later change might take to halve the bytes a scan reads. For each
seed this makes the cell's data and query pool as a run does, answers every
pool query with the reference computed in bfloat16, and hands those answers
to the same comparison a run makes. The comparison has to reject them: each
seed's line shows ``wrong_answers`` above its limit of 0. Needs no chip.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from mdrqbench import check, gen, harness, loads, reference, specs  # noqa: E402


def control_answers(cell, seed: int, seconds: float) -> dict:
    """Check the bfloat16 answers for one seed -> the check's output."""
    import ml_dtypes
    data_ss, query_ss, _, sample_ss = np.random.SeedSequence(seed).spawn(4)
    cols = gen.load(cell.cfg["generator"]).build(
        cell.cfg, np.random.default_rng(data_ss))
    traffic = cell.traffic
    pool_n = (max(1, int(round(traffic["rate_qps"] * seconds)))
              if traffic["loop"] == "open" else int(traffic["pool"]))
    lower, upper = harness.make_pool(cell, cols,
                                     np.random.default_rng(query_ss), pool_n)
    log = loads.Log()
    drawn = check.draw(np.arange(pool_n), np.ones(pool_n, bool),
                       list(range(pool_n)), {}, traffic["check"],
                       np.random.default_rng(sample_ss))
    kind = traffic["spec"]["kind"]
    drawn = set(drawn.tolist())
    for i in range(pool_n):
        k = log.add(i, 0.0, 0.0)
        log.t_done[k] = 0.0
        if i in drawn:
            ids = reference.match_ids(cols, lower[i], upper[i],
                                      dtype=ml_dtypes.bfloat16)
            log.result[k] = specs.load(kind).answer(ids, cols)
    return check.check(cols, lower, upper, log, np.arange(pool_n), {}, kind,
                       traffic["check"], np.random.default_rng(sample_ss))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control_answers(cell, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "n_checked": out["n_checked"],
                          **{k: v for k, v in out["limits"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
