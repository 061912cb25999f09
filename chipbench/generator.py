"""Traffic from a seed: the one generator every traffic mix goes through.

A mix is a data file, ``traffic/<name>.json``.  Its ``loop`` says how load
is offered:

* ``"closed"`` — one caller pushes ``chunk`` samples on each of
  ``channels`` channels, back to back, through the engine named by
  ``engine`` (``"single"``: `FilterBankEngine`; ``"sharded"``:
  `ShardedFilterBankEngine` over every chip the cell holds).  An optional
  ``engine_args`` object is passed to the engine's constructor as keyword
  arguments; its ``mesh``, ``[n_bank, n_data]``, becomes a device mesh
  (``"engine_args": {"mesh": [1, 4], "data_mode": "time"}``).
* ``"open"`` — ``tenants`` sessions, each selecting ``rows_per_tenant``
  contiguous rows of the bank, send chunks on a schedule fixed in
  advance, at ``rate_chunks_per_s`` in all, through a `BankSessionServer`
  with ``lanes`` lanes and a journal (``journal_fsync``: one fsync per
  step).  ``warm_depths`` are the numbers of chunks a lane may hold at
  once, warmed up before the window.  Optional keys shape the schedule:

  - ``rate_skew``: tenant i's share of the rate goes as 1/(i+1)**skew
    (Zipf; 0, the default, is equal rates);
  - ``burst``: ``{"on_s": a, "off_s": b}``, every tenant sends only in the
    first ``a`` seconds of each period of ``a + b``;
  - ``chunk_sizes``: ``{"median": m, "sigma": s, "min": lo, "max": hi}``,
    log-normal chunk sizes clipped to [lo, hi] (else every chunk is
    ``chunk`` samples, which the warm-up always uses).

A mix that needs more than this vocabulary brings ``traffic/<name>.py``
beside its JSON, with ``arrivals(seed, mix, seconds)`` (returning what
`arrivals` returns) and/or ``make_engine(program, mix, channels,
chunk_hint)``; the drivers call those in place of their own.

The seed draws the uniform samples and the order of the arrival gaps and
sizes.  Every seed gives the same amount of work: each tenant sends the
same number of chunks of the same set of sizes, and its gaps are the
same set of exponential quantiles, in an order drawn from the seed.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from statistics import NormalDist

import numpy as np

SAMPLES, ARRIVALS, CHECKED = 1, 2, 3  # independent streams from one seed


def load(root: pathlib.Path, name: str) -> dict:
    with open(root / "traffic" / f"{name}.json") as f:
        return json.load(f)


def hooks(root: pathlib.Path, name: str):
    """The module ``traffic/<name>.py``, or None where the mix has none."""
    path = root / "traffic" / f"{name}.py"
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location(
        "chipbench_traffic_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def samples(gen: np.random.Generator, shape, bits: int) -> np.ndarray:
    """Uniform signed ``bits``-bit samples, as int8 or int16."""
    dtype = np.int8 if bits <= 8 else np.int16
    return gen.integers(-(1 << (bits - 1)), 1 << (bits - 1), shape,
                        dtype=dtype)


def chunk_pool(seed: int, n: int, channels: int, chunk: int,
               bits: int) -> np.ndarray:
    """(n, channels, chunk) samples; a closed loop's push k sends
    ``pool[k % n]``."""
    return samples(rng(seed, SAMPLES), (n, channels, chunk), bits)


def session_streams(seed: int, sessions: int, length: int,
                    bits: int) -> np.ndarray:
    """(sessions, length) samples: each tenant's whole stream."""
    return samples(rng(seed, SAMPLES), (sessions, length), bits)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _sizes(mix: dict, n: int) -> np.ndarray:
    spec = mix.get("chunk_sizes")
    if spec is None:
        return np.full(n, int(mix["chunk"]), np.int64)
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    s = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(s), int(spec["min"]), int(spec["max"])) \
        .astype(np.int64)


def _on_time(t: np.ndarray, burst: dict | None) -> np.ndarray:
    """Wall time of ``t`` seconds of sending time under on/off bursts."""
    if burst is None:
        return t
    on, off = float(burst["on_s"]), float(burst["off_s"])
    return np.floor(t / on) * (on + off) + np.mod(t, on)


def arrivals(seed: int, mix: dict,
             seconds: float) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(due times in seconds from the window's start, tenant of each,
    size of each), sorted by time.  Tenant i sends round(rate · seconds ·
    share_i) chunks (at least one); its gaps are that many exponential
    quantiles, scaled to fill its sending time, and its sizes that many
    quantiles of the size distribution, each set shuffled by the seed."""
    tenants = int(mix["tenants"])
    share = 1.0 / np.arange(1, tenants + 1) ** float(mix.get("rate_skew", 0))
    share /= share.sum()
    total = float(mix["rate_chunks_per_s"]) * seconds
    burst = mix.get("burst")
    sending = seconds if burst is None else seconds * float(burst["on_s"]) \
        / (float(burst["on_s"]) + float(burst["off_s"]))
    gen = rng(seed, ARRIVALS)
    due, who, size = [], [], []
    for i in range(tenants):
        n = max(1, round(total * share[i]))
        gaps = -np.log1p(-_quantiles(n))
        gaps *= sending / gaps.sum()
        t = np.cumsum(gen.permutation(gaps)) - gaps.min()
        due.append(_on_time(t, burst))
        who.append(np.full(n, i))
        size.append(gen.permutation(_sizes(mix, n)))
    due, who, size = map(np.concatenate, (due, who, size))
    order = np.argsort(due, kind="stable")
    return due[order], who[order], size[order]
