"""Env-gated cProfile for the control-plane daemons.

``RAY_TPU_PROFILE_DIR=<dir>`` makes the head and agent profile their
entire lifetime and dump ``<name>-<pid>.pstats`` on clean shutdown
(SIGTERM). This is the instrument behind the multi-client loop analysis
(git history, `c35bee1`: PROFILE_MULTICLIENT.md; load the dumps with
``pstats.Stats`` and sort by own time): where do the head/agent asyncio
loops spend time while 4 clients submit task batches (reference analog:
the asio event-stats instrumentation, src/ray/common/asio + debug_state
dumps).
"""

from __future__ import annotations

import os
from typing import Optional


def maybe_start() -> Optional[object]:
    prof_dir = os.environ.get("RAY_TPU_PROFILE_DIR")
    if not prof_dir:
        return None
    import cProfile

    prof = cProfile.Profile()
    prof.enable()
    return prof


def dump(prof: Optional[object], name: str) -> None:
    if prof is None:
        return
    prof_dir = os.environ.get("RAY_TPU_PROFILE_DIR")
    if not prof_dir:
        return
    try:
        prof.disable()
        os.makedirs(prof_dir, exist_ok=True)
        prof.dump_stats(
            os.path.join(prof_dir, f"{name}-{os.getpid()}.pstats"))
    except Exception:
        pass
