"""Serving launcher: seeded requests through the engine (optionally behind
the gateway) on any --arch — the reduced variant by default, published
widths with --full.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --policy sagesched --n-requests 12

``start`` builds the engine and offers the requests, ``finish`` drains
them; ``main`` is the two in a row.  ``chip_smoke.py`` at the repo root
drives the same two functions.
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass

import numpy as np

from ..configs import ARCH_IDS, get_config
from ..core import Scheduler, make_policy
from ..core.policies import POLICY_NAMES
from ..data import ByteTokenizer
from ..models import build_model
from ..serving import (Gateway, GatewayConfig, ServeRequest, ServingEngine,
                       Verdict)
from .compile_cache import use_compile_cache

__all__ = ["parse_args", "make_requests", "start", "finish", "main",
           "Session"]

_TOPICS = ("summarize the report", "write a story", "explain the code",
           "translate the phrase")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_IDS)
    ap.add_argument("--policy", default="sagesched", choices=POLICY_NAMES)
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(8, 64),
                    metavar=("LO", "HI"),
                    help="prompt lengths, drawn uniformly from [LO, HI]")
    ap.add_argument("--new-tokens", type=int, nargs=2, default=(8, 48),
                    metavar=("LO", "HI"),
                    help="max new tokens, drawn uniformly from [LO, HI]")
    ap.add_argument("--temperature", type=float, default=0.6,
                    help="sampling temperature (0 = greedy; 0.6 is the "
                         "paper's default)")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=192)
    ap.add_argument("--step-mode", default="fused",
                    choices=("fused", "orchestrated"),
                    help="fused = one jitted device call per decode "
                         "(multi-)step; orchestrated = host-side loop")
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="decode tokens per host round-trip (fused mode)")
    ap.add_argument("--tp", type=int, default=1,
                    help="mesh-parallel width (sharded KV pool + expert "
                         "parallelism, bit-identical output — docs/"
                         "sharded_serving.md).  Needs tp devices: on a "
                         "CPU box set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--parallel", default="exact",
                    choices=("exact", "efficient"),
                    help="exact = bit-identical sharding (KV pool + "
                         "experts only); efficient = Megatron column/row-"
                         "parallel projections + vocab-sharded lm_head + "
                         "LSE-split attention, tolerance-based parity "
                         "(docs/sharded_serving.md 'Efficient mode')")
    ap.add_argument("--device-memory-gb", type=float, default=None,
                    help="per-device HBM budget for the build-time memory "
                         "preflight (refuses configs that cannot fit one "
                         "shard; default: no check)")
    ap.add_argument("--full", action="store_true",
                    help="published widths and depth instead of the "
                         "reduced smoke variant; qwen2-1.5b fits one TPU "
                         "v5e, larger configs need --tp")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the depth to this many layers, widths "
                         "unchanged (default: the config's own depth); "
                         "the launcher's one depth cut, for runs that "
                         "must fit a time or memory limit")
    ap.add_argument("--gateway", action="store_true",
                    help="serve through the bounded-admission gateway "
                         "(ACCEPT/QUEUE/SHED + deadlines + retries) "
                         "instead of raw submit")
    ap.add_argument("--max-inflight", type=int, default=None,
                    help="gateway in-flight cap (default 4 * n_slots)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="gateway per-tenant queue bound")
    ap.add_argument("--shed-policy", default="cost",
                    choices=("cost", "tail"),
                    help="cost = shed worst predicted-cost quantile; "
                         "tail = FCFS tail-drop")
    ap.add_argument("--ttft-deadline", type=float, default=None,
                    help="abort if first token misses this many seconds")
    ap.add_argument("--ttlt-deadline", type=float, default=None,
                    help="abort if last token misses this many seconds")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="retry budget for shed requests")
    return ap.parse_args(argv)


def make_requests(args: argparse.Namespace, vocab_size: int,
                  t0: float) -> list[ServeRequest]:
    """Seeded requests: prompt token ids drawn uniformly from the
    vocabulary's non-special ids, lengths from ``--prompt-len`` and
    ``--new-tokens``."""
    tok = ByteTokenizer()
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.n_requests):
        plen = int(rng.integers(args.prompt_len[0], args.prompt_len[1] + 1))
        reqs.append(ServeRequest(
            request_id=f"req-{i}",
            prompt=f"{_TOPICS[i % len(_TOPICS)]} case {i}",
            prompt_tokens=[int(t) for t in
                           rng.integers(tok.offset, vocab_size, plen)],
            max_new_tokens=int(rng.integers(args.new_tokens[0],
                                            args.new_tokens[1] + 1)),
            temperature=args.temperature,
            eos_token=tok.eos_id, arrival=t0 + i * 0.01,
            ttft_deadline_s=args.ttft_deadline,
            ttlt_deadline_s=args.ttlt_deadline))
    return reqs


@dataclass
class Session:
    engine: ServingEngine
    gateway: Gateway | None
    requests: list[ServeRequest]
    verdicts: list[Verdict]


def start(args: argparse.Namespace) -> Session:
    """Build the engine (and the gateway, with ``--gateway``) and offer
    every request; nothing has been decoded yet."""
    cfg = get_config(args.arch, reduced=not args.full)
    if args.n_layers is not None:
        cfg = cfg.with_overrides(n_layers=args.n_layers)
    if cfg.family == "encdec":
        raise SystemExit("the CLI serving demo drives decoder-only archs; "
                         "see tests/test_models_smoke.py for enc-dec paths")
    engine = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy(args.policy)),
        n_slots=args.n_slots, max_seq_len=args.max_seq_len, seed=0,
        step_mode=args.step_mode, decode_steps=args.decode_steps,
        tp=args.tp, parallel=args.parallel,
        device_memory_gb=args.device_memory_gb)
    reqs = make_requests(args, cfg.vocab_size, engine.clock())
    gw, verdicts = None, []
    if args.gateway:
        gw = Gateway(engine, GatewayConfig(
            max_inflight=args.max_inflight,
            max_queue_per_tenant=args.max_queue,
            max_total_queue=4 * args.max_queue,
            shed_policy=args.shed_policy,
            max_retries=args.max_retries))
        verdicts = gw.offer_batch(reqs)
    else:
        engine.submit_batch(reqs)
    return Session(engine, gw, reqs, verdicts)


def finish(session: Session) -> dict:
    """Serve every offered request to a terminal state; returns the
    engine's metrics summary."""
    if session.gateway is not None:
        session.gateway.run_until_drained()
    else:
        session.engine.run_until_done()
    return session.engine.metrics.summary(session.requests)


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    session = start(args)
    if session.engine.plan is not None:
        print(f"mesh: {session.engine.sharding_report()}")
    summary = finish(session)
    if session.gateway is not None:
        v = session.verdicts
        print(f"gateway verdicts: { {x.value: v.count(x) for x in set(v)} }")
    print(f"arch={session.engine.model.cfg.name} policy={args.policy} "
          f"{summary}")


if __name__ == "__main__":
    main()
