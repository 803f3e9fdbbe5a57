"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, the
configuration's reference runs once over each sampled request's prompt
and served tokens (teacher-forced, float32, ``highest`` precision, on
weights regenerated from the seed).  At each served position it reads
the reference's best logit and the logit of the token the program
served there; the numbers compared are the widest gap between them over
every sampled token and their mean.  A token id outside the vocabulary
reads as an infinite gap.

A control reads the same gap for the token that the reference computed
one precision lower (``int8`` or ``fp8`` weights per output channel,
bfloat16 activations) puts first at each position of the same streams.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["gaps", "ROW_BLOCK"]

ROW_BLOCK = 256


@jax.jit
def _score(h, head, served):
    """Per position: reference best logit, the served token's logit, the
    row's spread (best - worst) and the row's argmax."""
    rows = h.shape[0]
    hb = h.reshape(rows // ROW_BLOCK, ROW_BLOCK, h.shape[1])
    sb = served.reshape(rows // ROW_BLOCK, ROW_BLOCK)

    def one(args):
        x, s = args
        lg = x @ head
        best = lg.max(-1)
        val = jnp.take_along_axis(lg, jnp.clip(s, 0, lg.shape[1] - 1)[:, None],
                                  1)[:, 0]
        val = jnp.where((s >= 0) & (s < lg.shape[1]), val, -jnp.inf)
        return best, val, best - lg.min(-1), jnp.argmax(lg, -1)

    best, val, spread, arg = jax.lax.map(one, (hb, sb))
    return (best.reshape(-1), val.reshape(-1), spread.reshape(-1),
            arg.reshape(-1))


def _rows(pad_to: int) -> int:
    return -(-pad_to // ROW_BLOCK) * ROW_BLOCK


def _pad_rows(h, rows: int):
    if h.shape[0] == rows:
        return h
    return jnp.pad(h, ((0, rows - h.shape[0]), (0, 0)))


def gaps(ref, cfg: dict, w: dict, streams, pad_to: int,
         controls: tuple = ()) -> dict:
    """``streams``: [(prompt_tokens, served_tokens)].  Returns the widest
    gap (absolute logits and as a share of the row's spread), the mean
    gap, the number of tokens compared, and for each mode in
    ``controls`` the same for that control's first-ranked tokens
    (``<mode>_gap``, ``<mode>_mean_gap``)."""
    rows = _rows(pad_to)
    with jax.default_matmul_precision("highest"):
        head32 = ref.head(w, cfg, "float32")
        heads = {m: ref.head(w, cfg, m) for m in controls}

    def score(h, head, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.device_get(_score(h, head, jnp.asarray(tokens)))

    g_abs, g_share, n = [], [], 0
    c_abs = {m: [] for m in controls}
    c_share = {m: [] for m in controls}
    for prompt, served in streams:
        toks = list(prompt) + list(served[:-1])
        lo, m = len(prompt) - 1, len(served)
        rows_of = slice(lo, lo + m)
        sv = np.full(rows, -1, np.int32)
        sv[rows_of] = served
        h = _pad_rows(ref.final_hidden(w, cfg, toks, pad_to=pad_to), rows)
        best, val, spread, _ = (x[rows_of] for x in score(h, head32, sv))
        g_abs.append(best - val)
        g_share.append((best - val) / spread)
        n += m
        for m in controls:
            hc = _pad_rows(ref.final_hidden(w, cfg, toks, pad_to=pad_to,
                                            mode=m), rows)
            arg = score(hc, heads[m], sv)[3]
            cs = np.full(rows, -1, np.int32)
            cs[rows_of] = arg[rows_of]
            cval = score(h, head32, cs)[1][rows_of]
            c_abs[m].append(best - cval)
            c_share[m].append((best - cval) / spread)
    out = {"tokens": n}
    if n:
        out["gap"] = float(np.max(np.concatenate(g_abs)))
        out["mean_gap"] = float(np.mean(np.concatenate(g_abs)))
        out["gap_share"] = float(np.max(np.concatenate(g_share)))
        out["argmax_share"] = float(np.mean(np.concatenate(g_abs) == 0))
        for m in controls:
            out[f"{m}_gap"] = float(np.max(np.concatenate(c_abs[m])))
            out[f"{m}_mean_gap"] = float(np.mean(np.concatenate(c_abs[m])))
            out[f"{m}_gap_share"] = float(np.max(np.concatenate(c_share[m])))
            out[f"{m}_argmax_share"] = float(
                np.mean(np.concatenate(c_abs[m]) == 0))
    return out
