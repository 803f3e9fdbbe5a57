"""Open-loop traffic from a mix file: the benchmark's one generator.

A mix (``chipbench/traffic/<name>.json``) is data only: the datasets it
draws from (length medians, spreads, prompt templates), their shares, the
arrival rate with optional bursts, the share of greedy requests, the
serving deployment it is offered to (slots, context limit, KV pool) and
how long warm traffic runs before the window.

The generator's cluster model is the one ``repro.simulator.workload``
uses (semantic clusters, lognormal lengths with a short-answer mode,
Poisson arrivals with periodic bursts), copied here so that no change to
the program can move the yardstick.  Like the original, it draws each
gap at the rate in force where the gap starts, which under-draws a burst
shorter than a gap at the base rate (PERF.md, section 7).

The population (arrival times and the multiset of request sizes) comes
from the mix's own ``population_seed`` and is the same for every run.
``--seed`` permutes which request lands on which arrival, within the
warm, window and tail segments separately, and draws the prompt token
ids, so every seed offers the same work in another order.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass, field

import numpy as np

__all__ = ["Cluster", "TrafficRequest", "make_clusters", "population",
           "assign", "history_records", "seed_rng"]

# a compact word bank; clusters draw disjoint-ish vocab subsets from it
_WORDS = (
    "model train data neural layer token sample batch learn logic matrix "
    "vector tensor graph node edge path search sort merge hash tree heap "
    "stack queue list array string parse regex compile link load store fetch "
    "cache memory disk file socket packet route server client thread lock "
    "mutex atomic async await yield stream buffer pixel image audio video "
    "frame codec signal filter noise wave photon atom molecule protein gene "
    "cell tissue organ heart brain nerve blood bone muscle skin liver kidney "
    "story dragon castle knight wizard forest river mountain ocean island "
    "city village market bridge tower garden temple palace desert winter "
    "summer spring autumn morning evening night shadow light colour music "
    "poem novel essay letter report summary review article chapter verse "
    "contract clause statute court judge jury verdict appeal motion brief "
    "revenue profit margin equity asset bond stock option future hedge risk"
).split()


def seed_rng(seed: int, salt: int = 0) -> np.random.Generator:
    """numpy generator for any whole-number seed (negative or past 64
    bits included), kept apart from other uses of the same seed by
    ``salt``."""
    s = int(seed)
    words = [s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, int(s < 0), salt]
    return np.random.default_rng(np.random.SeedSequence(words))


@dataclass
class Cluster:
    """Semantically similar prompts sharing an output-length law."""

    cluster_id: str
    dataset: str
    template: str
    vocab: list[str]
    input_mu: float
    input_sigma: float
    output_mu: float
    output_sigma: float
    short_prob: float
    short_lo: int = 8
    short_hi: int = 96
    mutation: float = 0.15

    def sample_prompt(self, rng: np.random.Generator, n_free: int = 12) -> str:
        n_mut = int(round(n_free * self.mutation))
        words = list(rng.choice(self.vocab, size=n_free - n_mut))
        words += list(rng.choice(_WORDS, size=n_mut))
        rng.shuffle(words)
        return self.template + " " + " ".join(words)

    def sample_input_len(self, rng: np.random.Generator, cap: int) -> int:
        return int(np.clip(int(rng.lognormal(self.input_mu,
                                             self.input_sigma)), 8, cap))

    def sample_output_len(self, rng: np.random.Generator, cap: int) -> int:
        if self.short_prob > 0.0 and rng.random() < self.short_prob:
            return int(rng.integers(self.short_lo, self.short_hi + 1))
        return int(np.clip(int(rng.lognormal(self.output_mu,
                                             self.output_sigma)), 4, cap))


def make_clusters(ds: dict) -> list[Cluster]:
    """Per-cluster length laws drawn around the dataset's medians
    (x0.4 .. x2.2), seeded by the dataset's name so that every mix that
    names a dataset sees the same clusters."""
    name = ds["name"]
    rng = np.random.default_rng(zlib.crc32(name.encode()) % (2 ** 31))
    sp_lo, sp_hi = ds.get("short_prob", [0.05, 0.35])
    out = []
    for k in range(int(ds.get("clusters", 12))):
        vocab = list(rng.choice(_WORDS, size=18, replace=False))
        topic = " ".join(rng.choice(vocab, size=4, replace=False))
        imed = ds["input_median"] * float(rng.uniform(0.4, 2.2))
        omed = ds["output_median"] * float(rng.uniform(0.4, 2.2))
        osig = ds["output_sigma"] * float(rng.uniform(0.6, 1.3))
        out.append(Cluster(
            cluster_id=f"{name}-{k}", dataset=name,
            template=f"{ds['template']} {topic} [{name}-{k}]", vocab=vocab,
            input_mu=float(np.log(imed)), input_sigma=float(
                ds.get("input_sigma", 0.25)),
            output_mu=float(np.log(omed)), output_sigma=osig,
            short_prob=float(rng.uniform(sp_lo, sp_hi))))
    return out


@dataclass
class TrafficRequest:
    index: int
    due_s: float              # seconds after the start of warm traffic
    prompt: str
    input_len: int
    output_len: int           # emulated by max_new_tokens
    greedy: bool
    dataset: str
    segment: str = ""         # "warm" | "window" | "tail"
    tokens: np.ndarray | None = field(default=None, repr=False)


def _draw(mix: dict, clusters: list[list[Cluster]], rng) -> tuple:
    shares = np.array([d.get("share", 1.0) for d in mix["datasets"]], float)
    j = int(rng.choice(len(shares), p=shares / shares.sum()))
    c = clusters[j][int(rng.integers(len(clusters[j])))]
    serve = mix["serve"]
    in_cap = min(int(mix["datasets"][j].get("max_input", 8192)),
                 serve["max_seq_len"] - 2)
    prompt = c.sample_prompt(rng)
    n_in = c.sample_input_len(rng, in_cap)
    # the engine ends a request once its context reaches max_seq_len - 1
    n_out = c.sample_output_len(rng, max(1, serve["max_seq_len"] - 1 - n_in))
    return prompt, n_in, n_out, c.dataset


def population(mix: dict, seconds: float) -> list[TrafficRequest]:
    """Every request due from the start of warm traffic to the end of
    the tail after the window, from the mix's ``population_seed``."""
    rng = np.random.default_rng(int(mix["population_seed"]))
    clusters = [make_clusters(d) for d in mix["datasets"]]
    burst = mix.get("burst") or {}
    factor = float(burst.get("factor", 1.0))
    period = float(burst.get("period_s", 10.0))
    duty = float(burst.get("duty", 0.2))
    warm = float(mix["warm_s"])
    horizon = warm + seconds + float(mix.get("tail_s", 60.0))
    rps = float(mix["rate_rps"])
    greedy_share = float(mix.get("greedy_share", 0.0))
    t, out = 0.0, []
    while True:
        rate = rps * factor if (t % period) < duty * period else rps
        t += float(rng.exponential(1.0 / rate))
        if t >= horizon:
            break
        prompt, n_in, n_out, ds = _draw(mix, clusters, rng)
        greedy = bool(rng.random() < greedy_share)
        seg = "warm" if t < warm else ("window" if t < warm + seconds
                                       else "tail")
        out.append(TrafficRequest(len(out), t, prompt, n_in, n_out, greedy,
                                  ds, seg))
    return out


def assign(pop: list[TrafficRequest], seed: int, vocab: int,
           first_id: int = 3) -> list[TrafficRequest]:
    """This run's requests: the population's payloads permuted within
    each segment by ``seed``, and prompt token ids drawn from
    [first_id, vocab) by ``seed``."""
    rng = seed_rng(seed, salt=1)
    out = []
    for seg in ("warm", "window", "tail"):
        slots = [r for r in pop if r.segment == seg]
        perm = rng.permutation(len(slots))
        for r, j in zip(slots, perm):
            src = slots[j]
            out.append(TrafficRequest(
                r.index, r.due_s, src.prompt, src.input_len, src.output_len,
                src.greedy, src.dataset, seg))
    out.sort(key=lambda r: r.due_s)
    for r in out:
        r.tokens = rng.integers(first_id, vocab, r.input_len, dtype=np.int64)
    return out


def history_records(mix: dict) -> tuple[list[str], list[int], list[int]]:
    """A draw of the same mix disjoint from the population (its own
    seed), as the completions a deployment that has been serving this
    traffic would remember: (prompts, input lengths, output lengths)."""
    rng = np.random.default_rng(int(mix["population_seed"]) + 1)
    clusters = [make_clusters(d) for d in mix["datasets"]]
    prompts, ins, outs = [], [], []
    for _ in range(int(mix.get("history_records", 0))):
        prompt, n_in, n_out, _ = _draw(mix, clusters, rng)
        prompts.append(prompt)
        ins.append(n_in)
        outs.append(n_out)
    return prompts, ins, outs
