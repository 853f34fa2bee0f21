"""The comparisons that decide ``correct``, against ``bench/reference.py``.

Request cells: every reply is one of three kinds, and each is held to what
the service owes it.

* A representative (neither a cache hit nor coalesced) was solved on the
  device for its own environment: its cut, and the float64 price of its
  mask, must equal the reference MCOP cut of that environment (clamped to
  all-local).  A sample drawn from the seed is solved by the reference.
* A coalesced follower carries its tick's representative of the same bin:
  its cut, and the price of its mask, must be the float64 price of that
  representative's mask at the follower's own environment, clamped.
* A cache hit carries the mask that the bin's latest representative
  before its tick stored, priced and clamped the same way.

The number compared is the widest relative gap,
max(|cut - expected|, |price(mask) - expected|) / max(1, |expected|).

Session cells: the reference replays each connection's session engine
decisions (arrivals, departures, the 10% drift test, the cooldown), and
then: the number of due sessions of every tick must equal the report's;
a session that was not due keeps its cut exactly; a due session's cut must
be the reference placement of its bin's representative priced at its own
environment.  A bin's representative is the first due session of the bin
(slot order) in the first tick in which some connection saw the bin due,
so each connection contributes one candidate per bin; the gap is taken to
the nearest candidate.
"""

from __future__ import annotations

import numpy as np

from bench import reference

BLOCK = 256  # graphs per reference block: bounds host memory at n = 256


def _expected_from(profile, envs, masks):
    """float64 price of ``masks`` at ``envs``, clamped: (cut, mask, no_offload)."""
    cuts = np.empty(len(envs))
    out = np.array(masks, bool)
    for lo in range(0, len(envs), BLOCK):
        sl = slice(lo, lo + BLOCK)
        wl, wc, adj = reference.build(profile, envs[sl])
        partial = reference.price(wl, wc, adj, out[sl])
        cuts[sl], out[sl] = reference.clamp(partial, out[sl], wl.sum(axis=1))
    return cuts, out


def _prices(profile, envs, masks):
    prices = np.empty(len(envs))
    for lo in range(0, len(envs), BLOCK):
        sl = slice(lo, lo + BLOCK)
        wl, wc, adj = reference.build(profile, envs[sl])
        prices[sl] = reference.price(wl, wc, adj, np.asarray(masks[sl], bool))
    return prices


def _gap(cut, priced, expected):
    return np.maximum(np.abs(cut - expected), np.abs(priced - expected)) / np.maximum(1.0, np.abs(expected))


def solve_reference(profile, envs, rnd=reference.identity):
    cuts, masks = np.empty(len(envs)), np.empty((len(envs), len(profile["t_local"])), bool)
    for lo in range(0, len(envs), BLOCK):
        sl = slice(lo, lo + BLOCK)
        cuts[sl], masks[sl] = reference.solve(profile, envs[sl], rnd)
    return cuts, masks


def check_requests(profile: dict, replies: list[dict], *, sample: int, seed: int) -> dict:
    """Numbers compared for a request cell.

    ``replies``: every reply the run received, in submission order, each
    with ``env`` (6,), ``cut``, ``mask``, ``cache_hit``, ``coalesced``,
    ``tick``.  Returns {"placement_gap", "unexplained", "checked"}.
    """
    envs = np.array([r["env"] for r in replies], np.float64).reshape(-1, 6)
    cuts = np.array([r["cut"] for r in replies], np.float64)
    masks = np.array([r["mask"] for r in replies], bool).reshape(len(replies), -1)
    hit = np.array([r["cache_hit"] and not r["coalesced"] for r in replies], bool)
    fol = np.array([r["coalesced"] for r in replies], bool)
    ticks = np.array([r["tick"] for r in replies], np.int64)
    keys = [tuple(k) for k in reference.bin_keys(envs).tolist()]
    reps = np.nonzero(~hit & ~fol)[0]

    gaps = np.zeros(len(replies))
    checked = np.zeros(len(replies), bool)
    rng = np.random.default_rng([seed, 9])
    chosen = reps if len(reps) <= sample else np.sort(rng.choice(reps, sample, replace=False))
    if chosen.size:
        ref_cut, _ = solve_reference(profile, envs[chosen])
        gaps[chosen] = _gap(cuts[chosen], _prices(profile, envs[chosen], masks[chosen]), ref_cut)
        checked[chosen] = True

    # the mask each follower or hit must carry: its bin's representative
    rep_of_tick = {(ticks[i], keys[i]): i for i in reps}
    history: dict[tuple, list[tuple[int, int]]] = {}
    for i in reps:
        history.setdefault(keys[i], []).append((ticks[i], i))
    source = np.full(len(replies), -1)
    for i in np.nonzero(fol | hit)[0]:
        if fol[i]:
            source[i] = rep_of_tick.get((ticks[i], keys[i]), -1)
        else:
            earlier = [j for t, j in history.get(keys[i], ()) if t < ticks[i]]
            source[i] = earlier[-1] if earlier else -1
    unexplained = int(np.count_nonzero((fol | hit) & (source < 0)))
    derived = np.nonzero((fol | hit) & (source >= 0))[0]
    if derived.size:
        # a clamp decided the other way on rounding costs nothing: the
        # gap compares prices, and a wrong mask shows in its price
        exp_cut, _ = _expected_from(profile, envs[derived], masks[source[derived]])
        gaps[derived] = _gap(cuts[derived], _prices(profile, envs[derived], masks[derived]), exp_cut)
        checked[derived] = True
    return {
        "placement_gap": float(gaps.max()) if checked.any() else float("nan"),
        "unexplained": unexplained,
        "checked": int(checked.sum()),
    }


def check_sessions(profile: dict, connections: list[list[dict]], *, threshold: float,
                   min_interval: int, rnd=reference.identity) -> dict:
    """Numbers compared for a session cell.

    ``connections``: per connection, every cycle in order, each with
    ``envs`` (cap, 6), ``arrived``/``departed`` (cap,) masks and the
    report's ``due`` and ``min_cut`` (cap,).
    Returns {"session_gap", "due_mismatch", "kept_cut_changed", "checked"}.
    """
    never = 10**9
    due_rows = []  # (connection, cycle, slot)
    due_mismatch = kept_changed = 0
    for c, cycles in enumerate(connections):
        cap = len(cycles[0]["envs"])
        anchor = np.zeros((cap, 3))
        since = np.full(cap, never, np.int64)
        has = np.zeros(cap, bool)
        active = np.zeros(cap, bool)
        prev = np.full(cap, np.nan)
        for i, cy in enumerate(cycles):
            active &= ~cy["departed"]
            arr = cy["arrived"]
            anchor[arr], since[arr], has[arr], active[arr] = 0.0, never, False, True
            since[active] += 1
            exceeded = reference.drift_exceeded(anchor, cy["envs"], threshold)
            due = active & (~has | (exceeded & (since >= min_interval)))
            anchor[due] = cy["envs"][due, :3]
            since[due] = 0
            has |= due
            cut = np.asarray(cy["min_cut"], np.float64)
            due_mismatch += abs(int(due.sum()) - int(cy["due"]))
            kept = active & ~due
            same = (cut[kept] == prev[kept]) | (np.isnan(cut[kept]) & np.isnan(prev[kept]))
            kept_changed += int(np.count_nonzero(~same))
            prev = np.where(active, cut, prev)
            due_rows.extend((c, i, s) for s in np.nonzero(due)[0])
    if not due_rows:
        return {"session_gap": float("nan"), "due_mismatch": due_mismatch,
                "kept_cut_changed": kept_changed, "checked": 0}
    rows = np.array(due_rows)
    envs = np.stack([connections[c][i]["envs"][s] for c, i, s in due_rows])
    cuts = np.array([connections[c][i]["min_cut"][s] for c, i, s in due_rows], np.float64)
    keys = [tuple(k) for k in reference.bin_keys(envs).tolist()]
    # one candidate representative per (bin, connection): the first due
    # session of the bin in that connection's first tick that saw it
    candidate: dict[tuple, int] = {}
    for j, (c, i, _) in enumerate(rows):
        candidate.setdefault((keys[j], c), j)
    cand_rows = np.array(sorted(candidate.values()))
    _, cand_mask = solve_reference(profile, envs[cand_rows], rnd)
    mask_of = dict(zip(cand_rows.tolist(), cand_mask))
    by_bin: dict[tuple, list[int]] = {}
    for (key, _), j in candidate.items():
        by_bin.setdefault(key, []).append(j)
    gap = np.full(len(rows), np.inf)
    members: dict[tuple, list[int]] = {}
    for j, key in enumerate(keys):
        members.setdefault(key, []).append(j)
    for key, js in members.items():
        js = np.array(js)
        for cand in by_bin[key]:
            exp_cut, _ = _expected_from(profile, envs[js], np.broadcast_to(mask_of[cand], (len(js), len(mask_of[cand]))))
            g = np.abs(cuts[js] - exp_cut) / np.maximum(1.0, np.abs(exp_cut))
            gap[js] = np.minimum(gap[js], g)
    return {
        "session_gap": float(gap.max()),
        "due_mismatch": due_mismatch,
        "kept_cut_changed": kept_changed,
        "checked": int(len(rows)),
    }
