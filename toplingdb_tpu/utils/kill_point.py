"""Whitebox crash points — the reference's TEST_KILL_RANDOM mechanism
(util/kill_point? no: test_kill_random in /root/reference's
test_util/sync_point.h + db_crashtest.py whitebox mode, e.g.
version_set.cc:5769): named markers inside durability-critical code
self-kill the process with env-seeded probability, so the crash-recovery
matrix covers the exact windows between WAL append, memtable publish, SST
write and MANIFEST install.

Environment:
  TPULSM_KILL_ODDS    fire with probability 1/odds per marker (unset/0 = off)
  TPULSM_KILL_SEED    RNG seed (default: nondeterministic)
  TPULSM_KILL_PREFIX  comma-separated marker-name prefixes to arm (default:
                      all markers)

A fired marker exits with status 137 (the kill -9 status the blackbox
crash loop already expects), skipping all atexit/flush handlers — a real
crash, not a clean shutdown.
"""

from __future__ import annotations

import os
import random

KILLED_EXIT_CODE = 137

_state: tuple | None = None  # (odds, rng, prefixes)


def _load() -> tuple:
    global _state
    spec = os.environ.get("TPULSM_KILL_ODDS", "")
    try:
        odds = int(spec) if spec else 0
    except ValueError:
        odds = 0
    seed_spec = os.environ.get("TPULSM_KILL_SEED", "")
    rng = random.Random(int(seed_spec)) if seed_spec else random.Random()
    prefixes = tuple(
        p for p in os.environ.get("TPULSM_KILL_PREFIX", "").split(",") if p
    )
    _state = (odds, rng, prefixes)
    return _state


def test_kill_random(name: str) -> None:
    """Marker: maybe die here. Negligible when unarmed (one tuple check)."""
    st = _state if _state is not None else _load()
    odds, rng, prefixes = st
    if not odds:
        return
    if prefixes and not any(name.startswith(p) for p in prefixes):
        return
    if rng.randrange(odds) == 0:
        os._exit(KILLED_EXIT_CODE)


def armed(name: str) -> bool:
    """May the marker `name` fire in this process? (A path that would skip
    the marker takes the one that passes it while this holds.)"""
    st = _state if _state is not None else _load()
    odds, _, prefixes = st
    return bool(odds) and (not prefixes
                           or any(name.startswith(p) for p in prefixes))


def reset_for_tests() -> None:
    """Re-read the environment (tests flip env vars mid-process)."""
    global _state
    _state = None
