"""Persistence for a tuning decision cache.

The per-program knob cache (``transpiler/autotune.py``) persists as JSON
(``{"version": 1, "entries": {key: entry}}``) under this discipline:

* load tolerates a missing/corrupt file with a loud warning (never an
  exception at consult time) and drops malformed entries;
* save persists SEARCHED entries only (seeded defaults are
  deterministic heuristics — nothing to remember, and a pinned CI
  cache must never gain them), MERGES with what is on disk first so
  concurrent processes sharing one path don't drop each other's
  searched keys (ours still override), and lands atomically via
  ``os.replace``.
"""

import json
import os

__all__ = ["load_entries", "save_entries"]


def load_entries(path, is_valid, label):
    """Entries dict from `path` (or {}): unreadable files warn and
    return empty; entries failing `is_valid(entry)` are dropped."""
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        import sys

        sys.stderr.write(
            "WARNING: %s %s unreadable (%r); starting empty\n"
            % (label, path, e))
        return {}
    entries = raw.get("entries", raw)
    if not isinstance(entries, dict):
        return {}
    return {k: v for k, v in entries.items()
            if isinstance(v, dict) and is_valid(v)}


def save_entries(path, entries, is_valid, label):
    """Persist the searched subset of `entries` to `path`, merged with
    the searched entries already on disk (ours override), atomically.
    Failures warn, never raise."""
    if not path:
        return
    tmp = path + ".tmp.%d" % os.getpid()
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        merged = {
            k: v for k, v in load_entries(path, is_valid, label).items()
            if v.get("searched")
        }
        merged.update({k: v for k, v in entries.items()
                       if v.get("searched")})
        with open(tmp, "w") as f:
            json.dump({"version": 1, "entries": merged},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        import sys

        sys.stderr.write(
            "WARNING: %s %s not persisted (%r)\n" % (label, path, e))
