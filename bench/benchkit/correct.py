"""The check that decides ``correct``: the served path against the reference.

Once the window has closed, a sample of its waves is drawn from the
seed among those with finished requests, always with the wave that holds
the longest finished request (padded prompt plus served tokens).  For
every finished request of those waves, on the request's row as the
engine saw it (the prompt left-padded with token 0 to the wave's
longest, then the served tokens fed back), the check holds each half of
the served path to the reference:

- ``boundary_err``: the layers before the split.  At every position the
  program's boundary tensor, as the round trip received it (the prefill
  crossing's row, then one decode crossing per fed-back token), against
  the reference's float32 boundary: the relative L2 error of the
  position's activation vector, widest over all positions.
- ``logit_gap``: the codec, the wire and the layers after the split,
  with the token choice.  The reference quantizes the program's
  boundary tensor with its own independently calibrated clip range and
  runs the layers after the split on that; the number is the widest gap
  by which a served token's reference logit lies below the reference's
  best at that position, past one bfloat16 step at the best: the
  configuration serves in bfloat16, and logits within one step of the
  best cannot be told apart in that dtype, so a sound program's choice
  among them is rounding and not a fault.

``control=True`` also reads the control: the reference computed in
float8 (every matmul operand, and the control's own clip range from a
float8 calibration), put in the program's place on the same rows.  Its
boundary is held to the float32 boundary, and the token its logits put
first at each position to the reference run on its boundary.
"""

from __future__ import annotations

import time

import numpy as np

from . import reference as R

ROW_ALIGN = 128
NUMBERS = ("boundary_err", "logit_gap")


def sample(records: list[dict], n_waves: int, seed: int) -> list[list[dict]]:
    """Finished requests of up to ``n_waves`` waves, the longest's first."""
    by_wave: dict[int, list[dict]] = {}
    for r in records:
        if r["done"] and r["tokens"]:
            by_wave.setdefault(r["wave"], []).append(r)
    if not by_wave:
        return []
    longest = max(by_wave, key=lambda w: max(
        r["padded_len"] + len(r["tokens"]) for r in by_wave[w]))
    rest = sorted(w for w in by_wave if w != longest)
    rng = np.random.default_rng([seed, 0x5EED])
    picked = [longest] + [rest[i] for i in rng.permutation(len(rest))][
        :max(n_waves - 1, 0)]
    return [by_wave[w] for w in picked]


def _align(n: int) -> int:
    return -(-n // ROW_ALIGN) * ROW_ALIGN


def row(r: dict, crossings: list[dict]):
    """One request's token row (1, S), its program boundary (1, S, d),
    the number of positions the program computed, and the first
    position read (the last prompt position).  Rows are padded at the
    end to multiples of ``ROW_ALIGN``, so few shapes compile; later
    positions never reach earlier ones under the causal mask."""
    plen, n = r["padded_len"], len(r["tokens"])
    used = plen + n - 1
    toks = np.zeros((1, _align(used)), np.int32)
    p = r["prompt"]
    toks[0, plen - len(p):plen] = p
    toks[0, plen:used] = r["tokens"][:-1]
    prefill, steps = crossings[0]["x"], crossings[1:]
    d = prefill.shape[-1]
    x = np.zeros((1, toks.shape[1], d), np.float32)
    x[0, :plen] = prefill[r["slot"]]
    for j in range(n - 1):
        x[0, plen + j] = steps[j]["x"][r["slot"], 0]
    return toks, x, used, plen - 1


def _widest_rel_err(x, ref, used) -> float:
    x, ref = np.asarray(x)[0, :used], np.asarray(ref)[0, :used]
    err = np.linalg.norm(x - ref, axis=-1)
    return float(np.max(err / np.maximum(np.linalg.norm(ref, axis=-1),
                                         1e-30)))


def bf16_step(v):
    """Spacing of bfloat16 (8 significant bits) at ``v``."""
    e = np.floor(np.log2(np.maximum(np.abs(v), np.finfo(np.float32).tiny)))
    return np.exp2(e - 7)


def _widest_gap(lg, served) -> float:
    n = len(served)
    lg = np.asarray(lg, np.float64)[0, :n]
    best = lg.max(axis=-1)
    gap = best - lg[np.arange(n), served] - bf16_step(best)
    return float(max(np.max(gap), 0.0))


def readings(conf: dict, weights, waves: list[list[dict]],
             crossings: dict[int, list[dict]], crange,
             control_crange=None) -> dict:
    """The program's numbers, and the control's if asked for."""
    prog = dict.fromkeys(NUMBERS, 0.0)
    ctl = dict.fromkeys(NUMBERS, 0.0)
    n_tok = 0
    for wave in waves:
        for r in wave:
            # one row at a time: the reference's float32 activations and
            # scores of a whole wave need not fit beside the weights
            toks, x, used, start = row(r, crossings[r["wave"]])
            n = len(r["tokens"])
            at = {"start": start}
            ref_x = R.boundary(conf, weights, toks)
            prog["boundary_err"] = max(prog["boundary_err"],
                                       _widest_rel_err(x, ref_x, used))
            lg = R.tail_logits(conf, weights, R.codec(conf, x, crange),
                               **at)
            prog["logit_gap"] = max(prog["logit_gap"],
                                        _widest_gap(lg, r["tokens"]))
            n_tok += n
            if control_crange is None:
                continue
            cx = R.boundary(conf, weights, toks, low=True)
            ctl["boundary_err"] = max(ctl["boundary_err"],
                                      _widest_rel_err(cx, ref_x, used))
            pick = np.asarray(R.tail_logits(
                conf, weights, R.codec(conf, cx, control_crange), low=True,
                **at))[0, :n].argmax(axis=-1)
            lg = R.tail_logits(conf, weights, R.codec(conf, cx, crange),
                               **at)
            ctl["logit_gap"] = max(ctl["logit_gap"],
                                       _widest_gap(lg, pick))
    return {"program": prog, "tokens": n_tok,
            "control": ctl if control_crange is not None else None}


def check(conf: dict, workload: dict, weights, records: list[dict],
          crossings: list[dict], seed: int, *, control: bool = False,
          log=print) -> dict:
    t0 = time.perf_counter()
    waves = sample(records, workload["check_waves"], seed)
    if not waves:
        log("check: no finished request in the window")
        return {"program": None, "control": None, "tokens": 0}
    by_wave: dict[int, list[dict]] = {}
    for c in crossings:
        by_wave.setdefault(c["wave"], []).append(c)
    crange = R.calibrate(conf, weights)
    ctl_range = R.calibrate(conf, weights, low=True) if control else None
    out = readings(conf, weights, waves, by_wave, crange, ctl_range)
    out["seconds"] = time.perf_counter() - t0
    log(f"check ({out['seconds']:.1f}s): {out['tokens']} served tokens of "
        f"{sum(len(w) for w in waves)} requests in {len(waves)} waves; "
        f"reference clip range [{crange[0]:.6f}, {crange[1]:.6f}]; "
        f"program {out['program']}"
        + (f"; control (float8, clip range [{ctl_range[0]:.6f}, "
           f"{ctl_range[1]:.6f}]) {out['control']}" if control else ""))
    return out
