"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished -- the longest of them and others drawn
from the seed -- runs through the plain reference (the ``Reference`` of
the configuration's architecture module, over ``bench.reference``):
each prompt with its served tokens, packed several to a row of one
fixed length per cell, so one compiled pass serves every run.  For every
served token
the number compared is the gap by which the reference's logit of that
token lies below the reference's best logit at its position; a greedy
server that computes what the reference computes serves gaps of rounding
size.  Each number that the cell's limits file (``limits/<cell>.json``)
names is held to its limit: ``served_gap``, the widest gap over the
sample, and ``mean_gap``, the mean gap over its served tokens.  The
widest gap is set by one near-tie and swings from seed to seed; where
the control does not read three times the program's widest, the cell
compares the mean, which separates them.

The control (``control_gap``) reads, at the same positions, the token the
fp8 forward puts first and its gap under the float32 reference.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

PAD_TO = 128
ROW_BLOCK = 256


def pick_sample(reqs, t1: float, n: int, seed: int):
    """The longest finished request and ``n - 1`` more drawn from the
    seed, among requests whose last token streamed by ``t1``."""
    done = [r for r in reqs if len(r["times"]) == r["n_new"]
            and r["times"][-1] <= t1]
    if not done:
        return []
    done.sort(key=lambda r: (-(r["prompt_len"] + r["n_new"]), r["rid"]))
    rest = done[1:]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 5])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [done[0]] + [rest[i] for i in sorted(pick)]


def row_len(traffic: dict) -> int:
    """Length of every reference row of a cell: its longest sequence,
    rounded up to ``PAD_TO``, so one compiled pass serves every sample."""
    return -(-traffic["engine"]["max_len"] // PAD_TO) * PAD_TO


def served_rows(sample, length: int):
    """The sample's sequences (prompt + served tokens but the last)
    packed into rows of ``length`` slots, longest first, each into the
    first row with room.  Each row is ``(tokens, positions, segments)``
    (1, length) -- pad slots in segment -1 -- with the row slots whose
    logits chose each served token, and those tokens."""
    seqs = [np.concatenate([r["prompt"], np.asarray(r["tokens"][:-1],
                                                    np.int32)])
            for r in sample]
    order = sorted(range(len(sample)), key=lambda i: -len(seqs[i]))
    rows = []                                  # [used, [sequence index]]
    for i in order:
        if len(seqs[i]) > length:
            raise ValueError(f"sequence of {len(seqs[i])} tokens exceeds "
                             f"the reference row of {length}")
        row = next((r for r in rows if r[0] + len(seqs[i]) <= length), None)
        if row is None:
            row = [0, []]
            rows.append(row)
        row[0] += len(seqs[i])
        row[1].append(i)
    out = []
    for _, members in rows:
        tok = np.zeros((1, length), np.int32)
        pos = np.zeros((1, length), np.int32)
        seg = np.full((1, length), -1, np.int32)
        at, slots, toks = 0, [], []
        for j, i in enumerate(members):
            n, r = len(seqs[i]), sample[i]
            tok[0, at:at + n] = seqs[i]
            pos[0, at:at + n] = np.arange(n)
            seg[0, at:at + n] = j
            p0 = at + r["prompt_len"] - 1
            slots += range(p0, p0 + r["n_new"])
            toks += r["tokens"]
            at += n
        out.append(((tok, pos, seg), np.asarray(slots), np.asarray(toks)))
    return out


def _served_hidden(ref, rows, mode="f32"):
    """Final hidden rows at every served token's slot, and the served
    tokens, in row order."""
    hs = ref.hidden_rows([r[0] for r in rows], mode)
    h = jnp.concatenate([x[0, slots] for x, (_, slots, _) in
                         zip(hs, rows)])
    return h, np.concatenate([r[2] for r in rows])


def _gaps(ref, h_rows, chosen):
    """Per row: the reference's best logit minus its logit of the
    ``chosen`` token, from final hidden rows, in blocks of rows."""
    n = h_rows.shape[0]
    pad = -n % ROW_BLOCK          # one logits shape: whole blocks of rows
    h_rows = jnp.pad(h_rows, ((0, pad), (0, 0)))
    chosen = np.pad(np.asarray(chosen), (0, pad))
    out = []
    for s in range(0, n + pad, ROW_BLOCK):
        lg = ref.logits(h_rows[s:s + ROW_BLOCK])
        c = jnp.asarray(chosen[s:s + ROW_BLOCK])
        picked = jnp.take_along_axis(lg, c[:, None], axis=1)[:, 0]
        out.append(np.asarray(jnp.max(lg, axis=1) - picked))
    return np.concatenate(out)[:n]


def served_gap(arch, cfg: dict, seed: int, sample, length: int) -> dict:
    """Widest reference-logit gap of the sample's served tokens, the
    reference of ``arch`` running over rows of ``length`` slots."""
    ref = arch.Reference(cfg, seed)
    h, toks = _served_hidden(ref, served_rows(sample, length))
    gaps = _gaps(ref, h, toks)
    return {"served_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "tokens": int(len(toks)), "requests": len(sample)}


def control_gap(arch, cfg: dict, seed: int, sample, length: int) -> dict:
    """Widest gap of the tokens the fp8 control puts first, and the
    program's own served gap, at the same positions."""
    ref = arch.Reference(cfg, seed)
    rows = served_rows(sample, length)
    h, toks = _served_hidden(ref, rows)
    hc, _ = _served_hidden(ref, rows, "fp8")
    pad = -hc.shape[0] % ROW_BLOCK
    hcp = jnp.pad(hc, ((0, pad), (0, 0)))
    ctrl = np.concatenate([
        np.asarray(jnp.argmax(ref.logits(hcp[s:s + ROW_BLOCK], mode="fp8"),
                              axis=1))
        for s in range(0, hcp.shape[0], ROW_BLOCK)])[:hc.shape[0]]
    prog, fp8 = _gaps(ref, h, toks), _gaps(ref, h, ctrl)
    return {"served_gap": float(prog.max()), "mean_gap": float(prog.mean()),
            "control_gap": float(fp8.max()),
            "control_mean_gap": float(fp8.mean()),
            "tokens": int(len(toks)), "requests": len(sample)}


def judge(checks: dict) -> bool:
    """Every compared number present and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
