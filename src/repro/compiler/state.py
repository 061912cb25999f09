"""Streaming tail state as a first-class compiler artifact.

Overlap-save streaming keeps exactly one piece of mutable state per
engine: the last ``taps − 1`` input samples of every channel, plus the
stream's sample counters.  `TailSnapshot` freezes that state and keys
it to the **content digest of the compiled program** (`BlmacProgram.key`)
— restoring a snapshot into an engine built from a different bank is a
loud `ValueError`, never a silently wrong stream.

Because the tail is pure host-side numpy, a snapshot is a complete,
deterministic replay point: re-running ``concat(tail, chunk)`` through
ANY backend of the same program reproduces the chunk's outputs bit-
exactly.  That property is what makes the sharded engine's fault
recovery bit-exact — on shard loss it re-partitions the bank over the
surviving mesh and replays every in-flight chunk from its snapshot
(`repro.filters.ShardedFilterBankEngine`), and it is what a serving
process saves beside `BlmacProgram.save()` to resume a stream across a
restart (`save()`/`load()` here use the same atomic npz + JSON-header
layout).
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

from ..core.io import atomic_write, check_format_header

__all__ = ["STATE_FORMAT_VERSION", "SnapshotFormatError", "TailSnapshot"]

STATE_FORMAT_VERSION = 1


class SnapshotFormatError(ValueError):
    """A saved tail-snapshot file has the wrong kind/version or is
    corrupted — recapture the snapshot (or start a fresh stream)."""


@dataclasses.dataclass(frozen=True)
class TailSnapshot:
    """Frozen overlap-save stream state, content-addressed to a program.

    ``program_key`` is the hex digest of the `BlmacProgram` the stream
    was running; ``tail`` is the (channels, ≤ taps−1) int32 history;
    ``samples_in`` / ``samples_out`` are the stream counters at capture
    time.  Engines validate the key and channel count on restore.

    ``session`` is an optional caller-chosen stream identity: the
    multi-tenant session server (`repro.serving.sessions`) stamps each
    parked/paused session's id here, so a directory of snapshots is
    self-describing — which tenant a frozen stream belongs to rides
    with the artifact, not in a side table.  Engines ignore it; files
    written before the field existed load with ``session=""``.

    ``sample_bits`` is the stream's sample width (16, 8, or None before
    the stream's first push), which a restoring engine adopts; files
    written before the field existed load with None.
    """

    program_key: str
    channels: int
    samples_in: int
    samples_out: int
    tail: np.ndarray
    session: str = ""
    sample_bits: int | None = None

    def save(self, path) -> None:
        """Atomic npz write (`repro.core.io.atomic_write`), mirroring
        `BlmacProgram.save` — a killed process never leaves a truncated
        snapshot behind."""
        header = {
            "format_version": STATE_FORMAT_VERSION,
            "kind": "blmac_tail_snapshot",
            "program_key": self.program_key,
            "channels": int(self.channels),
            "samples_in": int(self.samples_in),
            "samples_out": int(self.samples_out),
            "session": str(self.session),
            "sample_bits": self.sample_bits,
        }
        atomic_write(path, lambda f: np.savez(
            f,
            header=np.array(json.dumps(header)),
            tail=np.asarray(self.tail, np.int32),
        ))

    @classmethod
    def load(cls, path) -> "TailSnapshot":
        """Read a snapshot written by `save`; every way the file can be
        bad raises `SnapshotFormatError`."""
        try:
            with np.load(path, allow_pickle=False) as z:
                header = json.loads(str(z["header"][()]))
                check_format_header(
                    header, kind="blmac_tail_snapshot",
                    version=STATE_FORMAT_VERSION, path=path,
                    error_cls=SnapshotFormatError, label="tail-snapshot",
                )
                tail = np.ascontiguousarray(z["tail"], np.int32)
        except SnapshotFormatError:
            raise
        except Exception as e:  # truncated zip, missing array, bad JSON …
            raise SnapshotFormatError(f"{path}: unreadable snapshot: {e}")
        if tail.ndim != 2 or tail.shape[0] != int(header["channels"]):
            raise SnapshotFormatError(
                f"{path}: tail shape {tail.shape} does not match header "
                f"channels={header['channels']}"
            )
        return cls(
            program_key=str(header["program_key"]),
            channels=int(header["channels"]),
            samples_in=int(header["samples_in"]),
            samples_out=int(header["samples_out"]),
            tail=tail,
            session=str(header.get("session", "")),
            sample_bits=header.get("sample_bits"),
        )
