"""How ``correct`` is decided: the served tokens, or the first training
steps, against the plain reference.

A sample of the finished requests, drawn from the seed and always holding
the one with the most served tokens, is run through the reference
(``bench/reference.py``) once each, prompt and served tokens together.
At every position that produced a served token, the gap by which that
token's reference logit lies below the reference's best logit is read;
the widest gap over the sample is the number compared. A greedy token the
program computed right has gap 0, a near-tie flipped by the program's
rounding a small one.

The control (``control=True``) reads, at the same positions, the gap of
the token that the reference computed with float8 weights puts first, and
puts that gap through the same ``passed`` limit (``control_correct``).
"""
from __future__ import annotations

import numpy as np

from bench import model, reference


def sample(finished: list, seed: int, tokens: int, max_requests: int) -> list:
    """Requests to compare: the one with most served tokens, then others in
    a seeded order until ``tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.req.generated),
                                            r.arrival.uid))
    rest = order[1:]
    rng = model.host_rng(seed, 30)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [order[0]], len(order[0].req.generated)
    for r in rest:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.req.generated)
    return out


def gaps(params, profiles: dict, conf: dict, picked: list, length: int,
         control: bool = False) -> dict:
    """Per-position logit gaps of the served tokens (and of the control's
    first choice) under the float32 reference."""
    import jax.numpy as jnp

    s = reference.hashable_sizes(model.sizes(conf))
    served, ctrl = [], []
    for rec in picked:
        prompt = np.asarray(rec.req.prompt, np.int32)
        gen = np.asarray(rec.req.generated, np.int32)
        seq = np.concatenate([prompt, gen[:-1]])
        pos = len(prompt) - 1 + np.arange(len(gen))
        probes = np.zeros((length, 2), np.int32)
        probes[pos, 0] = gen
        probes[pos, 1] = gen
        toks = jnp.asarray(reference.pad_to(seq, length))
        pid = int(rec.req.profile_id)
        if control:
            ad8 = reference.profile_adapters(params, profiles, pid, s,
                                             fp8=True)
            _, _, amax8 = reference.logits_probe(
                params, *ad8, toks, jnp.asarray(probes), s=s, fp8=True)
            probes[pos, 1] = np.asarray(amax8)[pos]
        ad = reference.profile_adapters(params, profiles, pid, s)
        best, at, _ = reference.logits_probe(params, *ad, toks,
                                             jnp.asarray(probes), s=s)
        best, at = np.asarray(best)[pos], np.asarray(at)[pos]
        served.append(best - at[:, 0])
        ctrl.append(best - at[:, 1])
    served = np.concatenate(served) if served else np.zeros(0)
    ctrl = np.concatenate(ctrl) if ctrl else np.zeros(0)
    out = {"logit_gap_max": float(served.max()) if len(served) else None,
           "tokens_compared": int(len(served)),
           "requests_compared": len(picked)}
    if control:
        out["control_gap_max"] = float(ctrl.max()) if len(ctrl) else None
    return out


def serving(run, params, profiles: dict, finished: list,
            control: bool = False) -> dict:
    """The numbers compared in a serving run, each with its limit:
    ``{name: [value, limit]}``."""
    c = run.cell["check"]
    picked = sample(finished, run.seed, c["tokens"], c["max_requests"])
    got = gaps(params, profiles, run.conf, picked,
               run.cell["engine"]["max_seq"], control=control)
    limit = run.cell["limits"]["logit_gap_max"]
    if control:
        # the control in the program's place, through the same limit
        got["control_correct"] = passed(
            {"logit_gap_max": [got["control_gap_max"], limit]})
    run.info["check"] = got
    return {"logit_gap_max": [got["logit_gap_max"], limit]}


def training(run, frozen, first: dict, batches: list, prog: dict,
             control: bool = False) -> dict:
    """The numbers compared in an onboarding run, each with its limit: the
    program's first three steps against the reference's
    (``bench/train_reference.py``) from the same trainables, rows and
    step keys (``prog["rng_seed"]``, the training loop's first key).
    ``loss_gap``: the widest relative gap of a step's loss;
    ``grad1_gap``: the worst leaf's gap between the norms of the first
    clipped gradient; ``change3_gap``: the same for the change of the
    trainables over three steps, over the leaves the reference's first
    gradient moves. A number the cell gives no limit is read, not
    compared.

    ``control=True`` also reads the same numbers for the reference with
    float8 weights in the program's place, and for the fault of a step
    that averages half of each slot's rows, each put through the same
    limits."""
    import jax

    from bench import train_reference as TR

    R = run.cell["roster"]
    sizes = reference.hashable_sizes(model.sizes(run.conf))
    key = jax.random.key(prog["rng_seed"])
    kw = dict(lr=R["lr"], clip=R["clip"])
    ref = TR.run_steps(frozen, first, batches, key, sizes, **kw)
    got = TR.compare(prog, ref)
    limits = run.cell["limits"]
    checks = {k: [got[k], lim] for k, lim in limits.items()}
    if control:
        def reading(res):
            res["rows3"] = res["after"][-1]
            out = TR.compare(res, ref)
            out["correct"] = passed({k: [out[k], lim]
                                     for k, lim in limits.items()})
            return out

        got["control"] = reading(TR.run_steps(frozen, first, batches, key,
                                              sizes, fp8=True, **kw))
        m = batches[0]["tokens"].shape[1]
        got["half_rows"] = reading(TR.run_steps(
            frozen, first, batches, key, sizes, rows_used=m // 2, **kw))
    run.info["check"] = got
    return checks


def passed(checks: dict) -> bool:
    return bool(checks) and all(v is not None and v <= lim
                                for v, lim in checks.values())
