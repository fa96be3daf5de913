"""The comparison that decides `correct` for a training cell: the
program's (or the control's) readings of its first steps against the
plain reference's, one number and one limit each."""

from __future__ import annotations

import statistics


def leaf_gaps(got, ref):
    """Over the leaves, |norm got - norm ref| measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero): ``(worst, its detail, root
    mean square)``.  *got* and *ref* map the same leaf names to norms.
    The worst leaf swings from seed to seed by its nature; the root mean
    square over some hundreds of leaves is steady."""
    if set(got) != set(ref):
        raise ValueError("the leaves differ: %r" % sorted(
            set(got) ^ set(ref))[:4])
    floor = statistics.median(ref.values())
    worst, where, squares = 0.0, None, 0.0
    for name, r in ref.items():
        gap = abs(got[name] - r) / max(r, floor, 1e-30)
        squares += gap * gap
        if worst == worst and not gap <= worst:  # NaN is worst, and stays
            worst, where = gap, name
    detail = "%s: %.6g against %.6g, median leaf %.6g" % (
        where, got[where], ref[where], floor)
    return worst, detail, (squares / len(ref)) ** 0.5


def training_numbers(got, ref, names=None):
    """``number -> (value, detail)`` for the readings of
    `reference.common.follow_steps` (*ref*) and their counterparts read
    from the program's state (*got*, keyed by the program's parameter
    names; *names* maps reference names to those)."""
    names = names or {n: n for n in ref["first_update_norms"]}

    def keyed(norms):
        return {names[n]: v for n, v in norms.items()}

    steps = len(ref["losses"])
    loss_gap = max(abs(g - r) / abs(r) for g, r in
                   zip(got["losses"][:steps], ref["losses"]))
    first, at1, first_rms = leaf_gaps(got["first_update_norms"],
                                      keyed(ref["first_update_norms"]))
    whole, at3, whole_rms = leaf_gaps(got["total_update_norms"],
                                      keyed(ref["total_update_norms"]))
    leaves = "root mean square over %d leaves" % len(names)
    return {"first_update_difference": (
                ref["first_update_difference"],
                "|update - reference's| / |reference's|, all leaves "
                "together, step 1"),
            "loss_gap": (loss_gap, "program %s against %s" % (
                " ".join("%.6g" % v for v in got["losses"][:steps]),
                " ".join("%.6g" % v for v in ref["losses"]))),
            "first_gradient_norm_gap": (first, at1),
            "first_gradient_norm_rms": (first_rms, leaves),
            "update_norm_gap": (whole, at3),
            "update_norm_rms": (whole_rms, leaves)}


def keep_readings(path, got, ref, names=None):
    """Every leaf's norms of both sides, for reading by hand: a file, not
    a line of the result."""
    import json
    import os
    names = names or {n: n for n in ref["first_update_norms"]}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rows = {k: {n: [got[k][names[n]], ref[k][n]] for n in ref[k]}
            for k in ("first_update_norms", "total_update_norms")}
    rows["losses"] = [got["losses"], ref["losses"]]
    rows["first_update_difference"] = ref["first_update_difference"]
    with open(path, "w") as f:
        json.dump(rows, f)


def judge(numbers, limits):
    """Prints each number beside its limit; true when all are inside."""
    ok = True
    for name, (value, detail) in numbers.items():
        limit = limits[name]
        inside = value <= limit
        ok = ok and inside
        print("correct: %-26s %.6g  limit %.6g  %s  (%s)"
              % (name, value, limit, "ok" if inside else "OUTSIDE", detail),
              flush=True)
    return ok
