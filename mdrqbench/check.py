"""Whether the served answers are right: a sample against the numpy reference.

The sample is drawn from the seed out of the queries due in the window: up
to ``per_path`` answers served by each access path the planner used there,
then more queries at random up to ``sample``, so every path that served in
the window is checked. Every answer the window gave to a drawn query is
compared, however often the pool replayed it. Each number compared has a limit, and
the run is correct only when every number is at or below its limit:

* ``wrong_answers``: sampled answers that differ from the reference (exact
  comparison, limit 0);
* ``unchecked_paths``: paths that served in the window with no answer in the
  sample (limit 0).
"""
import numpy as np

from mdrqbench import reference, specs


def draw(ks: np.ndarray, ok: np.ndarray, pool_idx: list, method_of: dict,
         params: dict, rng: np.random.Generator) -> np.ndarray:
    """Log indices to check.

    Up to ``per_path`` answers served by each path, then more at random
    until ``sample`` distinct queries are drawn; every answer in the window
    to a drawn query is checked, since the pool is replayed.
    """
    cand = ks[ok]
    by_path = {}
    for k in cand:
        by_path.setdefault(method_of.get(int(k), "unknown"), []).append(int(k))
    pick = set()
    for name in sorted(by_path):
        idx = np.asarray(by_path[name])
        take = min(int(params["per_path"]), idx.size)
        pick.update(int(k) for k in rng.choice(idx, take, replace=False))
    queries = {pool_idx[k] for k in pick}
    rest = np.asarray(sorted({pool_idx[k] for k in cand} - queries), np.int64)
    more = min(max(int(params["sample"]) - len(queries), 0), rest.size)
    if more:
        queries.update(int(i) for i in rng.choice(rest, more, replace=False))
    return np.asarray([k for k in cand if pool_idx[k] in queries], np.int64)


def check(cols, lower, upper, log, ks, method_of, spec_kind, params, rng,
          dtype=np.float32) -> dict:
    """Compare a sample of the window's answers with the reference."""
    kind = specs.load(spec_kind)
    ok = np.array([log.error[k] is None and log.t_done[k] is not None
                   for k in ks], bool)
    sample = draw(ks, ok, log.pool_idx, method_of, params, rng)
    want = {}
    wrong = 0
    by_path = {}
    for k in sample:
        i = log.pool_idx[k]
        if i not in want:
            ids = reference.match_ids(cols, lower[i], upper[i], dtype=dtype)
            want[i] = kind.answer(ids, cols)
        path = method_of.get(int(k), "unknown")
        by_path[path] = by_path.get(path, 0) + 1
        if not kind.same(log.result[k], want[i]):
            wrong += 1
    used = {method_of.get(int(k), "unknown") for k in ks[ok]}
    return {
        "n_checked": int(sample.size),
        "n_queries": len(want),
        "by_path": by_path,
        "limits": {
            "wrong_answers": {"value": wrong, "limit": 0},
            "unchecked_paths": {"value": len(used - set(by_path)),
                                "limit": 0},
        },
    }
