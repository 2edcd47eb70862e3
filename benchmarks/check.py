"""The comparison that decides ``correct``: what the timed path produced
against the plain reference. Each number compared has a limit of its own
in the configuration's file (``"limits"``), set from readings on the chip
(PERF.md gives them); a number is reported beside its limit in every run.
"""

from __future__ import annotations

import importlib
import statistics


def reference_for(cfg: dict):
    return importlib.import_module(f"benchmarks.reference.{cfg['family']}")


def _verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """numbers: name -> reading. Every reading needs a limit and has to
    be a number at or under it."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and value == value and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok and bool(numbers), out


def _gap_numbers(gaps) -> dict:
    n_tok = sum(len(g) for g in gaps)
    return {"max_gap": float(max(g.max() for g in gaps)),
            "mean_gap": float(sum(g.sum() for g in gaps) / n_tok)}


def serve(cfg, seed, sample, control: bool = False):
    """``sample``: the served requests to follow (the driver's
    ``check_sample``). The numbers compared, over every served token of
    the sample: the widest gap by which a served token's logit lies below
    the reference's best, and the mean gap (which grows with the square
    of the noise: the share of tokens that flip times how far). No
    finished request to follow is not correct.

    ``control``: the reference in ``control_precision`` takes the
    program's place and goes through the same verdict; it has to come
    out as not correct (``control_correct`` false)."""
    if not sample:
        return False, {"served_requests": {"value": 0, "limit": ">= 1"}}, {}
    ref = reference_for(cfg)
    served = [(r.prompt, r.tokens) for r in sample]
    gaps, low = ref.serve_gaps(
        seed, cfg, served,
        precision=cfg["control_precision"] if control else "float32")
    ok, table = _verdict(_gap_numbers(gaps), cfg["limits"])
    extra = {"served_requests": len(sample),
             "served_tokens": int(sum(len(g) for g in gaps)),
             "tokens_off_best": int(sum((g > 0).sum() for g in gaps))}
    if low is not None:
        extra["control_correct"], extra["control"] = _verdict(
            _gap_numbers(low), cfg["limits"])
        extra["control_tokens_off_best"] = int(sum((g > 0).sum() for g in low))
    return ok, table, extra


def _worst_leaf_gap(got: dict, want: dict, keep=None) -> tuple[float, str]:
    """The gap between the program's norm and the reference's, leaf by
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger; the worst leaf and its name."""
    med = statistics.median(want.values())
    worst, name = 0.0, ""
    for k, w in want.items():
        if keep is not None and k not in keep:
            continue
        g = got.get(k)
        gap = float("inf") if g is None else abs(g - w) / max(w, med)
        if gap != gap:
            gap = float("inf")
        if gap >= worst:
            worst, name = gap, k
    return worst, name


def train_numbers(got: dict, want: dict) -> tuple[dict, dict]:
    """The numbers compared for a training cell, from the program's
    readings ``got`` and the reference's ``want`` (both: ``loss`` per
    step, ``grad_norm`` and ``delta_norm`` per leaf)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["loss"], want["loss"]))
    if len(got["loss"]) != len(want["loss"]):
        loss_gap = float("inf")
    grad_gap, grad_leaf = _worst_leaf_gap(got["grad_norm"], want["grad_norm"])
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: out of the change, by a rule on the
    # reference's gradient
    med = statistics.median(want["grad_norm"].values())
    moved = {k for k, g in want["grad_norm"].items() if g >= 1e-3 * med}
    delta_gap, delta_leaf = _worst_leaf_gap(got["delta_norm"],
                                            want["delta_norm"], moved)
    numbers = {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
               "delta_norm_gap": delta_gap}
    extra = {"grad_norm_worst_leaf": grad_leaf,
             "delta_norm_worst_leaf": delta_leaf,
             "leaves_left_out_of_delta": len(want["grad_norm"]) - len(moved),
             "loss": got["loss"], "reference_loss": want["loss"]}
    return numbers, extra


def train(cfg, spec, seed, rec, control: bool = False):
    ref = reference_for(cfg)
    n = int(spec["check_steps"])
    batches = [rec["batches"][k % len(rec["batches"])] for k in range(n)]
    hp = cfg["train"]["adamw"]
    want = ref.train_reference(seed, cfg, batches, hp=hp)
    numbers, extra = train_numbers(rec["readings"], want)
    ok, table = _verdict(numbers, cfg["limits"])
    if control:
        # the control and the planted fault take the program's place and
        # go through the same verdict: both have to come out not correct
        low = ref.train_reference(seed, cfg, batches, hp=hp,
                                  precision=cfg["control_precision"])
        extra["control_correct"], extra["control"] = _verdict(
            train_numbers(low, want)[0], cfg["limits"])
        half = ref.train_reference(seed, cfg, batches, hp=hp,
                                   half_batch=True)
        extra["fault_correct"], extra["fault_half_batch"] = _verdict(
            train_numbers(half, want)[0], cfg["limits"])
    return ok, table, extra
