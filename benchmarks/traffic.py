"""The one general traffic generator: a mix's data file + a seed -> requests.

A mix is ``traffic/<name>.json``.  It may name a base with ``"extends"``
(top-level keys of the base are taken unless the mix sets them), so one
content description serves an open-loop and a closed-loop mix.  The keys:

``prefix_tokens``   tokens every request starts with (a prompt template)
``docs``            optional shared chunks: ``pool`` chunks of ``len``
                    [lo, hi] tokens, ``per_request`` drawn without
                    replacement with Zipf(``zipf_s``) popularity, in draw
                    order (a ranked retrieval result)
``reask_share``     share of requests that repeat an earlier request's
                    chunks in the same order under a new unique part
``unique``          length of the part no other request shares
``max_tokens``      output length asked for
``temperature``, ``top_p``
``max_total``       prompt + output may not pass this (asserted)
``spec_requests``   how many request shapes the mix defines; a run
                    that needs more takes them again from the first
``arrivals``        ``{"loop": "open"}`` (the cell file gives ``rate_rps``)
                    or ``{"loop": "closed", "clients": n}``
``supply_rps``      how many requests to prepare per second of window
``warmup``          bursts that drive every program shape the mix uses
``reference_len``   [lo, hi] prompt lengths of the reference check

Steadiness: every seed runs the SAME work.  All sizes (chunk lengths,
which chunks a request takes, unique lengths, output lengths, arrival
gaps) and their order come from the mix's own ``shape_seed``;
``--seed`` only chooses the token values (and, in run.py, the weights).
With the order permuted by the seed ``itl_p95_ms`` ranged 8 % over three
seeds (PERF.md).

No JAX here: the load generator's parent imports this before it holds
the chip, and the tests run it alone.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    """Read ``traffic/<name>.json``, resolving ``extends``."""
    path = ROOT / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    base = mix.pop("extends", None)
    if base is not None:
        mix = {**load_mix(base), **mix}
    return mix


def _draw(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    """n whole numbers from a length distribution, clipped to [lo, hi]."""
    lo, hi = int(dist["lo"]), int(dist["hi"])
    kind = dist["dist"]
    if kind == "uniform":
        out = rng.integers(lo, hi + 1, size=n)
    elif kind == "lognormal":
        out = np.rint(
            rng.lognormal(math.log(dist["median"]), dist["sigma"], size=n)
        )
    elif kind == "fixed":
        out = np.full(n, int(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def request_shapes(mix: dict) -> dict:
    """Everything about the mix's requests but their token values: drawn
    from ``shape_seed`` alone, so it is the same for every ``--seed``."""
    rng = np.random.Generator(np.random.PCG64(int(mix["shape_seed"])))
    n = int(mix["spec_requests"])
    docs = mix.get("docs")
    doc_lens = np.zeros(0, dtype=np.int64)
    picks = [[] for _ in range(n)]
    if docs:
        pool = int(docs["pool"])
        doc_lens = _draw(rng, {"dist": "uniform", **docs["len"]}, pool)
        weights = 1.0 / np.arange(1, pool + 1) ** float(docs["zipf_s"])
        weights /= weights.sum()
        picks = [
            rng.choice(
                pool, size=int(docs["per_request"]), replace=False, p=weights
            ).tolist()
            for _ in range(n)
        ]
        share = float(mix.get("reask_share", 0.0))
        back = int(mix.get("reask_back", 8))
        for i in range(1, n):
            if rng.random() < share:
                picks[i] = list(picks[i - int(rng.integers(1, min(back, i) + 1))])
    unique = _draw(rng, mix["unique"], n)
    max_tokens = _draw(rng, mix["max_tokens"], n)
    gaps = rng.exponential(1.0, size=n)  # unit rate; the cell's rate scales it
    prefix = int(mix["prefix_tokens"])
    total = (
        prefix
        + np.array([sum(int(doc_lens[d]) for d in p) for p in picks])
        + unique
        + max_tokens
    )
    if int(total.max()) > int(mix["max_total"]):
        raise ValueError(
            f"a request of {int(total.max())} tokens passes max_total "
            f"{mix['max_total']}"
        )
    return {
        "doc_lens": doc_lens,
        "picks": picks,
        "unique": unique,
        "max_tokens": max_tokens,
        "gaps": gaps,
    }


def generate(
    mix: dict, seed: int, vocab: int, n_requests: int, rate_rps: float = 0.0
) -> list[dict]:
    """The requests of one run: ``prompt`` (token ids), ``max_tokens``,
    ``temperature``, ``top_p`` and, in an open loop, ``due`` seconds
    from the start of the window."""
    shapes = request_shapes(mix)
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    floor = int(mix.get("vocab_floor", 256))

    def tokens(k: int) -> list[int]:
        return rng.integers(floor, vocab, size=int(k)).tolist()

    prefix = tokens(mix["prefix_tokens"])
    doc_tokens = [tokens(k) for k in shapes["doc_lens"]]
    out = []
    due = 0.0
    for i in (k % int(mix["spec_requests"]) for k in range(n_requests)):
        prompt = list(prefix)
        for d in shapes["picks"][i]:
            prompt.extend(doc_tokens[d])
        prompt.extend(tokens(shapes["unique"][i]))
        req = {
            "prompt": prompt,
            "max_tokens": int(shapes["max_tokens"][i]),
            "temperature": float(mix["temperature"]),
            "top_p": float(mix["top_p"]),
        }
        if rate_rps > 0:
            due += float(shapes["gaps"][i]) / rate_rps
            req["due"] = due
        out.append(req)
    return out


def warmup_bursts(mix: dict, seed: int, vocab: int) -> list[list[dict]]:
    """The mix's warm-up plan as bursts of requests.  Each entry of a
    burst is ``{"count", "shared", "fresh", "max_tokens"}``: ``shared``
    tokens of one warm-up-only base sequence, then ``fresh`` new ones."""
    rng = np.random.Generator(np.random.PCG64(int(seed) + 1))
    floor = int(mix.get("vocab_floor", 256))
    base = rng.integers(floor, vocab, size=4096).tolist()
    bursts = []
    for step in mix.get("warmup", []):
        burst = []
        for entry in step["requests"]:
            for _ in range(int(entry.get("count", 1))):
                fresh = rng.integers(
                    floor, vocab, size=int(entry["fresh"])
                ).tolist()
                burst.append(
                    {
                        "prompt": base[: int(entry["shared"])] + fresh,
                        "max_tokens": int(entry["max_tokens"]),
                        "temperature": float(mix["temperature"]),
                        "top_p": float(mix["top_p"]),
                    }
                )
        bursts.append(burst)
    return bursts


def reference_prompts(mix: dict, seed: int, vocab: int, n: int) -> list[list]:
    """n seeded prompts for the float32 reference check."""
    rng = np.random.Generator(np.random.PCG64(int(seed) + 2))
    floor = int(mix.get("vocab_floor", 256))
    lo, hi = mix["reference_len"]
    return [
        rng.integers(floor, vocab, size=int(k)).tolist()
        for k in rng.integers(int(lo), int(hi) + 1, size=n)
    ]
