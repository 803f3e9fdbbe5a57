#!/usr/bin/env python3
"""Bring-up check on the chip: the served path, end to end, at published
widths with seeded weights.

    python chip_smoke.py              # one chip: qwen2-1.5b
    python chip_smoke.py --chips 4    # four chips: olmoe-1b-7b at tp=4

One chip: 16 seeded greedy requests (prompts of 64-1024 tokens, 16-128
new tokens) go through ``repro.launch.serve`` — Gateway -> Scheduler
(sagesched, numpy priorities) -> KVCacheManager -> fused ServingEngine —
and the script checks that every request finished and the engine
quiesced, that the compile count stays inside its bound, that the
Pallas Gittins kernel, compiled natively, agrees with the numpy backend
on the live queue and on seeded rows at the widest bucket count, and
that what was served agrees with a float32 reference of the same
weights: prefill logits of the first request, and every served token,
teacher-forced.

Four chips (this phase only): olmoe-1b-7b at full depth, tp=4, served in
parallel="exact" and parallel="efficient" on the same requests (checked
under ``assert_tokens_close``), and the config cut to 4 layers served at
tp=1 and at tp=4 exact, which must match token for token.  The logits
bound of each comparison applies to teacher-forced logits at every
generated position.

Everything runs in this one process.  Progress goes to earlier lines;
the last line is one JSON object naming the device.  Any failed check,
or a first device that is not a TPU, exits non-zero with no JSON line.
Times printed are set-up (compiles included), never a speed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the served path (bf16 weights, the chip's default matmul precision)
# against the same weights in float32 at "highest" precision, both
# teacher-forced on the served streams.  First request's prefill logits:
# the worst may differ by at most this share of the reference row's
# spread (max - min), and the argmax must agree.  Every served token:
# its reference logit lies within this share of the spread below the
# reference's best at that position (a token the reference ranks that
# close to its best is a near-tie, not a wrong cache or layout)
LOGIT_TOL = 0.05
# native Pallas (float32) against the float64 numpy Gittins backend, on
# the live queue (8 buckets) and on seeded rows of 256 buckets with costs
# to 1e5, where float32 prefix sums and re-origining at the attained
# cost alone account for 3.7e-4 (the interpret-mode kernel's own error)
GITTINS_RTOL = {"live queue": 1e-4, "seeded": 1e-3}

QWEN_ARGV = ["--arch", "qwen2-1.5b", "--full", "--gateway",
             "--n-requests", "16", "--prompt-len", "64", "1024",
             "--new-tokens", "16", "128", "--temperature", "0",
             "--n-slots", "8", "--max-seq-len", "1280"]
OLMOE_ARGV = ["--arch", "olmoe-1b-7b", "--full", "--gateway",
              "--n-requests", "8", "--prompt-len", "65", "128",
              "--new-tokens", "16", "32", "--temperature", "0",
              "--n-slots", "8", "--max-seq-len", "256"]
# prompts stay inside one prefill bucket (128) and contexts inside two
# table-width buckets, which keeps the four engines' compiles few
OLMOE_CUT_LAYERS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX's first device is "
                         f"{devices[0].platform!r}; this check never runs "
                         "on another backend")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX sees {len(devices)}")
    return devices


def serve(argv: list[str], label: str, between=None):
    """One launcher session: build, offer, (``between``), drain.  Checks
    that every request finished with tokens, the engine quiesced and the
    fused step stayed inside its compile bound."""
    from repro.launch import serve as launcher
    from repro.serving import RequestState
    from repro.testing.faults import assert_engine_quiesced

    t0 = time.monotonic()
    session = launcher.start(launcher.parse_args(argv))
    engine = session.engine
    built = time.monotonic() - t0
    if between is not None:
        between(session)
    summary = launcher.finish(session)
    served = time.monotonic() - t0 - built
    reqs = session.requests
    bad = [(r.request_id, r.state.value, r.finish_reason) for r in reqs
           if r.state != RequestState.FINISHED or not r.output_tokens]
    if bad:
        raise AssertionError(f"{label}: requests did not finish: {bad}")
    if session.gateway is not None:
        session.gateway.assert_all_terminal()
    assert_engine_quiesced(engine)
    compiles, bound = engine.fused_compile_count, engine.max_fused_compiles()
    log(f"{label}: build {built:.1f} s, serve {served:.1f} s (set-up: "
        "every compile is inside these; not a speed)")
    log(f"{label}: {summary['completed']}/{len(reqs)} requests finished, "
        f"{summary['decode_tokens']} tokens generated, "
        f"{summary['prefill_tokens']} prompt tokens prefilled, "
        f"preemptions {summary['preemptions']}")
    log(f"{label}: fused_compile_count {compiles} <= "
        f"max_fused_compiles() {bound}: {compiles <= bound}")
    if not compiles <= bound:
        raise AssertionError(f"{label}: {compiles} fused compiles exceed "
                             f"the bound {bound}")
    log(f"{label}: engine quiesced (KV blocks conserved, no live "
        "refcounts)")
    return session


def teacher_forced(engine, requests, streams, *, float32=False):
    """Each request's logits at every position of its stream, with the
    stream fed back as the tokens so far: one forward per request over
    prompt + stream padded to the engine's ``max_seq_len``, under the
    engine's own layout; ``float32`` casts the weights to float32 and
    runs at "highest" matmul precision.  Pads come last, so causal
    attention and the MoE's in-order capacity ranks leave real positions
    untouched.  Returns one (len(stream), vocab) float32 array each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    width = max(len(w) for w in streams)

    def fwd(p, t, start):
        if float32:
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        lg = engine.model.forward(p, {"tokens": t})[0][0]
        return jax.lax.dynamic_slice_in_dim(
            lg, start, width).astype(jnp.float32)

    fwd = (jax.jit if engine.plan is None else engine.plan.wrap_jit)(fwd)
    out = []
    with jax.default_matmul_precision("highest" if float32 else None):
        for r, w in zip(requests, streams):
            toks = np.zeros((1, engine.max_seq_len), np.int32)
            seq = list(r.prompt_tokens) + list(w[:-1])
            toks[0, :len(seq)] = seq
            p = len(r.prompt_tokens)
            out.append(np.asarray(fwd(engine.params, jnp.asarray(toks),
                                      p - 1))[:len(w)])
    return out


def memory(label: str, n: int) -> None:
    """Bytes in use now and the process's peak so far, per chip."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:n]]
    log(f"{label}: bytes_in_use per chip "
        f"{[s.get('bytes_in_use') for s in stats]}; process "
        f"peak_bytes_in_use per chip so far "
        f"{[s.get('peak_bytes_in_use') for s in stats]}")


def check_gittins(session) -> None:
    """The Pallas Gittins kernel, compiled for the chip, against the
    numpy backend: on the live queue's bucketized rows, and on seeded
    rows at the scheduler's widest bucket count (1024 x 256, a third of
    them conditioned on attained cost, some past their whole support).
    The live rows carry no attained cost: the scheduler re-evaluates a
    row every 200 generated tokens, past these requests' 128, so only
    the seeded rows test the conditioning."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.backends import (NumpyPriorityBackend,
                                     PallasPriorityBackend)
    from repro.kernels.gittins.ops import gittins_op

    def compare(label, sup, probs, att):
        want = NumpyPriorityBackend().gittins(sup, probs, att)
        got = PallasPriorityBackend().gittins(sup, probs, att)
        n, k = sup.shape
        n2 = max(8, 1 << (n - 1).bit_length())
        hlo = gittins_op.lower(jnp.zeros((n2, k), jnp.float32),
                               jnp.zeros((n2, k), jnp.float32)
                               ).compile().as_text()
        native = jax.default_backend() == "tpu" \
            and "tpu_custom_call" in hlo
        err = float(np.max(np.abs(got - want) / np.abs(want)))
        log(f"gittins, {label}: {n} rows x {k} buckets, attained > 0 in "
            f"{int((att > 0).sum())}; native kernel (tpu_custom_call in "
            f"the compiled HLO): {native}; max relative error vs numpy "
            f"{err:.3e} (bound {GITTINS_RTOL[label]})")
        if not native:
            raise AssertionError("gittins: the op did not compile to the "
                                 "native Pallas kernel")
        np.testing.assert_allclose(got, want, rtol=GITTINS_RTOL[label])

    # the rows the numpy backend evaluates: the scheduler's batched state
    st = session.engine.scheduler._state
    view = st.view(np.arange(st.n))
    compare("live queue", view.cost_sup, view.cost_probs, view.attained)
    rng = np.random.default_rng(0)
    n, k = 1024, 256
    sup = np.sort(rng.uniform(1, 1e5, (n, k)), axis=1)
    probs = rng.dirichlet(np.ones(k), n)
    att = rng.uniform(0, 1.2e5, n) * (rng.random(n) < 1 / 3)
    compare("seeded", sup, probs, att)


def check_reference(session) -> None:
    """What was served against float32 weights under "highest" matmul
    precision, every stream teacher-forced through both: the first
    request's prefill logits (the bf16 forward's and the reference's),
    and every served token against the reference's best at its
    position."""
    import numpy as np

    engine, reqs = session.engine, session.requests
    served = [list(r.output_tokens) for r in reqs]
    ref = teacher_forced(engine, reqs, served, float32=True)
    got = teacher_forced(engine, reqs[:1], served[:1])[0][0]
    want = ref[0][0]
    diff = float(np.max(np.abs(got - want)))
    spread = float(want.max() - want.min())
    agree = int(got.argmax()) == int(want.argmax())
    first = served[0][0] == int(want.argmax())
    log(f"reference: {reqs[0].request_id} ({len(reqs[0].prompt_tokens)} "
        f"prompt tokens) prefill max |logit - f32 reference| {diff:.4e} = "
        f"{diff / spread:.4e} of the reference spread {spread:.4e} "
        f"(bound {LOGIT_TOL}); argmax {int(got.argmax())} vs "
        f"{int(want.argmax())}: {agree}; first served token "
        f"{served[0][0]}: {first}")
    # each served token's distance below the reference's best, as a
    # share of the reference row's spread
    share = np.concatenate([
        (r.max(axis=1) - r[np.arange(len(w)), w])
        / (r.max(axis=1) - r.min(axis=1)) for r, w in zip(ref, served)])
    best = int((share == 0).sum())
    worst = int(np.argmax(share))
    log(f"reference: {best}/{share.size} served tokens are the f32 "
        f"reference's argmax at their position; the rest lie at most "
        f"{share[worst]:.4e} of the spread below it (bound {LOGIT_TOL})")
    if not (np.isfinite(got).all() and diff <= LOGIT_TOL * spread and agree
            and first):
        raise AssertionError("reference: prefill logits or the first "
                             "served token disagree with the float32 "
                             "reference")
    if not share[worst] <= LOGIT_TOL:
        raise AssertionError("reference: a served token is no near-tie "
                             "of the float32 reference's best")


def one_chip() -> None:
    def between(session):
        # prefills run and decode starts before the kernel sees the queue
        for _ in range(2):
            session.gateway.step()
        check_gittins(session)

    session = serve(QWEN_ARGV, "qwen2-1.5b", between)
    check_reference(session)
    memory("qwen2-1.5b", 1)


def four_chips() -> None:
    """Every engine runs and both comparisons are reported before any
    failure is raised, so one run shows the whole picture."""
    import numpy as np
    from repro.testing.tolerance import TokenMismatch, assert_tokens_close

    def run(extra, label, ref=None):
        """Serve, then teacher-force the reference's streams (this run's
        own when ``ref`` is None) through the same engine."""
        session = serve(OLMOE_ARGV + extra, label)
        out = [list(r.output_tokens) for r in session.requests]
        tf = teacher_forced(session.engine, session.requests,
                            out if ref is None else ref[0])
        memory(label, 4)
        del session
        gc.collect()
        memory(f"{label}, freed", 4)
        return out, tf

    def compare(label, got, want, **kw):
        """``assert_tokens_close`` on the streams, with the logits bound
        applied to the teacher-forced logits at every generated
        position.  A failure lists, per diverged stream, the reference's
        own margin between its token and the other run's, and the two
        runs' logit drift at that position: a margin inside the drift is
        a near-tie flip, a margin far outside it a wrong layout."""
        try:
            st = assert_tokens_close(
                got[0], want[0], logits=np.concatenate(got[1]),
                ref_logits=np.concatenate(want[1]), label=label, **kw)
        except TokenMismatch as e:
            drift = max(float(np.max(np.abs(g - w)))
                        for g, w in zip(got[1], want[1]))
            log(f"{label}: FAILED — {e}; teacher-forced max |logit "
                f"diff| {drift:.4e}")
            for m in e.mismatches:
                si, d = m["stream"], m["pos"]
                if m["want"] is None or m["got"] is None:
                    continue
                w, g = want[1][si][d], got[1][si][d]
                log(f"  stream {si} pos {d}: reference margin "
                    f"{w[m['want']] - w[m['got']]:.4e} "
                    f"({m['want']} over {m['got']}); other run's margin "
                    f"{g[m['want']] - g[m['got']]:.4e}; drift at this "
                    f"position {float(np.max(np.abs(g - w))):.4e}")
            return False
        log(f"{label}: {st}")
        return True

    exact = run(["--tp", "4", "--parallel", "exact"], "olmoe tp=4 exact")
    eff = run(["--tp", "4", "--parallel", "efficient"],
              "olmoe tp=4 efficient", ref=exact)
    ok = [compare("efficient vs exact (tp=4)", eff, exact)]
    del exact, eff
    cut = ["--n-layers", str(OLMOE_CUT_LAYERS)]
    one = run(cut, f"olmoe {OLMOE_CUT_LAYERS}L tp=1")
    four = run(cut + ["--tp", "4", "--parallel", "exact"],
               f"olmoe {OLMOE_CUT_LAYERS}L tp=4 exact", ref=one)
    ok.append(compare(f"exact tp=4 vs tp=1 ({OLMOE_CUT_LAYERS} layers)",
                      four, one, bit_identical=True, max_logit_diff=0.0))
    if not all(ok):
        raise AssertionError("four chips: a parity check failed (above)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the olmoe-1b-7b mesh phase only")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import use_compile_cache
    log(f"device: {devices[0].device_kind}, {len(devices)} visible; "
        f"compile cache {use_compile_cache()}")
    t0 = time.monotonic()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"total {time.monotonic() - t0:.1f} s, compiles included")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
