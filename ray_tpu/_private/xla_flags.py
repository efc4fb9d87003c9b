"""XLA_FLAGS hygiene shared by the test conftest and the bench harness.

jaxlib hard-aborts the whole process (``parse_flags_from_env.cc`` FATAL
"Unknown flags in XLA_FLAGS") the first time a backend initializes if
``XLA_FLAGS`` names a flag the build doesn't know. Tuning flags that were
valid for one jaxlib (collective rendezvous deadlines, eigen threading)
silently become process-killers after a toolchain bump — observed as a
SIGABRT mid-test-suite at the first driver-side jax computation.

``supported_xla_flags`` probes the CURRENT jaxlib in a scratch subprocess
and drops exactly the flags it rejects. The verdict is cached in /tmp
keyed by jaxlib version + flag set, so the ~seconds-long probe runs once
per toolchain, not once per pytest invocation.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import subprocess
import sys
import tempfile
from typing import List, Optional

_PROBE_SRC = (
    "import jax\n"
    "jax.config.update('jax_platforms', 'cpu')\n"
    "jax.devices()\n"
)


def _jaxlib_version() -> str:
    try:
        import jaxlib.version

        return jaxlib.version.__version__
    except Exception:
        return "unknown"


def _cache_path(flags: List[str]) -> str:
    key = hashlib.sha256(
        (" ".join(flags) + "::" + _jaxlib_version()).encode()).hexdigest()[:16]
    return os.path.join(tempfile.gettempdir(),
                        f"ray_tpu_xla_flag_probe_{key}.json")


def _probe_once(flags: List[str], timeout_s: float):
    """One backend-init probe run; returns the CompletedProcess or None
    when the probe itself couldn't run."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        return subprocess.run([sys.executable, "-c", _PROBE_SRC], env=env,
                              capture_output=True, text=True,
                              timeout=timeout_s)
    except Exception:
        return None


def _probe(flags: List[str], timeout_s: float) -> Optional[List[str]]:
    """Iteratively probe until a subset of ``flags`` passes backend init.

    Every candidate that gets CACHED has itself survived a probe — a
    filtered set can fail in a NEW way (dropping all ``--`` flags leaves
    a bare token leading, which XLA treats as a flags-file name and
    FATALs on), and caching such a set would crash every later run.
    Returns None when no verdict could be produced (keep flags as-is)."""
    cur = list(flags)
    for _ in range(4):
        if not cur:
            return cur
        r = _probe_once(cur, timeout_s)
        if r is None:
            return None
        if r.returncode == 0:
            return cur
        m = re.search(r"Unknown flags in XLA_FLAGS:([^\n]*)",
                      r.stderr + r.stdout)
        if m:
            unknown = set(m.group(1).split())
            nxt = [f for f in cur if f not in unknown]
        else:
            # fatal without a flag attribution (e.g. leading bare token
            # misread as a flags file): shed bare tokens, then give up
            nxt = [f for f in cur if f.startswith("--")]
        if nxt == cur:
            return []  # no progress: no tuning flags beats an abort
        cur = nxt
    return []


def normalize_xla_flags(value: str) -> str:
    """Order ``--``-prefixed flags before bare tokens: XLA treats a
    LEADING non-``--`` token as the name of a flags file and FATALs when
    it can't open it (parse_flags_from_env.cc:169). A leading token that
    IS an existing file is the documented flags-file form — leave the
    value untouched so we don't break it."""
    toks = value.split()
    if toks and not toks[0].startswith("--") and os.path.exists(toks[0]):
        return value
    return " ".join(sorted(toks, key=lambda t: not t.startswith("--")))


def supported_xla_flags(flags: List[str],
                        timeout_s: float = 120.0) -> List[str]:
    """Filter ``flags`` down to what the current jaxlib accepts."""
    flags = [f for f in flags if f]
    if not flags:
        return flags
    cache = _cache_path(flags)
    try:
        with open(cache) as f:
            kept = json.load(f)
        if isinstance(kept, list):
            return kept
    except (OSError, ValueError):
        pass
    kept = _probe(flags, timeout_s)
    if kept is None:
        return flags
    try:
        tmp = cache + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(kept, f)
        os.replace(tmp, cache)
    except OSError:
        pass
    return kept
