"""Tier-1 static check: no NEW silent exception swallowing in hetu_tpu.

``except ...: pass`` hides real failures — a wedged socket, a half-
written checkpoint, a dead worker — until they resurface somewhere
unrelated.  The resilience subsystem exists precisely because silent
failure paths turn recoverable faults into lost runs, so this gate
makes every swallow site EXPLICIT: the AST of every module under
``hetu_tpu/`` is scanned for except-handlers whose body is only
``pass``, and each hit must be on the reviewed allowlist below (these
are all best-effort cleanup: ``__del__``/``close`` teardown, cache
probes, optional telemetry).  Adding a new one means consciously adding
it here — with the same scrutiny these received.
"""

import ast
import os

import pytest

HETU_ROOT = os.path.join(os.path.dirname(__file__), "..", "hetu_tpu")

# Reviewed silent-pass sites, as "relative/path.py::enclosing_function".
# Every entry is best-effort cleanup or an optional probe where failure
# is genuinely uninteresting — NOT data-path error handling.
ALLOWED = {
    # optional env bootstrap / telemetry
    "profiler.py::save",                    # best-effort trace dump
    "logger.py::__init__",                  # wandb backend optional
    "parallel/search.py::maybe_record",     # profile cache write optional
    "galvatron/search.py::profile_hp_layers",   # falls back to analytic
    # teardown (__del__/close/stop run during interpreter shutdown)
    "dataloader.py::stop",
    "ps/preduce.py::__del__",
    "ps/store.py::__del__",
    "datasets/prefetch.py::close",
    "datasets/prefetch.py::__del__",
    # transport cleanup between retransmit attempts (the retry itself
    # surfaces the error; closing a dead socket can't fail usefully)
    "ps/rpc.py::_attempt",
    "ps/rpc.py::_heartbeat",                # probe loop; alive() reports
    "ps/rpc.py::close",
    # device/platform probes with safe fallbacks
    "graph/executor.py::_dispatch",         # copy_to_host_async optional
    # best-effort file cleanup around ATOMIC writes (the replace/rename
    # is the correctness step; removing a leftover .tmp cannot fail it)
    "graph/checkpoint.py::atomic_write_bytes",
    "resilience/checkpointer.py::save",     # retention prune best-effort
    "resilience/checkpointer.py::_save_ps_snapshots",  # .tmp cleanup
    # after the os.replace (or on a failed native save, where the
    # original error is already propagating)
    "resilience/faults.py::wrapped",        # closing a dead socket (goal)
    "datasets/_io.py::_once",               # .part cleanup post-replace
    "datasets/criteo.py::_cache_key",       # mtime probe, cache key only
    "datasets/criteo.py::process_criteo",   # stale-manifest invalidation
}


def _silent_pass_sites(root):
    sites = []
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, encoding="utf-8") as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError as e:
                    sites.append((f"{rel}::<syntax-error>", e.lineno))
                    continue

            def walk(node, funcname):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    funcname = node.name
                if isinstance(node, ast.ExceptHandler) and all(
                        isinstance(s, ast.Pass) for s in node.body):
                    sites.append((f"{rel}::{funcname}", node.lineno))
                for child in ast.iter_child_nodes(node):
                    walk(child, funcname)

            walk(tree, "<module>")
    return sites


def test_no_new_silent_except_pass():
    sites = _silent_pass_sites(HETU_ROOT)
    new = [f"{key} (line {line})" for key, line in sites
           if key not in ALLOWED]
    assert not new, (
        "new `except ...: pass` swallow site(s) in hetu_tpu/ — handle "
        "the error, log it, or (for genuine best-effort cleanup) add the "
        "site to the reviewed allowlist in tests/test_no_silent_except.py"
        ":\n  " + "\n  ".join(new))


def test_allowlist_not_stale():
    """Entries whose site disappeared must leave the allowlist, so it
    only ever shrinks toward zero tolerated swallows."""
    present = {key for key, _ in _silent_pass_sites(HETU_ROOT)}
    stale = sorted(ALLOWED - present)
    assert not stale, (
        "allowlist entries with no matching `except: pass` site — "
        "remove them from tests/test_no_silent_except.py:\n  "
        + "\n  ".join(stale))


def test_scanner_detects_swallows(tmp_path):
    """The scanner itself must flag a pass-only handler and accept a
    handled one (guards against the gate silently going blind)."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "def ok():\n"
        "    try:\n"
        "        pass\n"
        "    except ValueError as e:\n"
        "        raise RuntimeError('handled') from e\n"
        "def bad():\n"
        "    try:\n"
        "        pass\n"
        "    except Exception:\n"
        "        pass\n")
    sites = _silent_pass_sites(str(tmp_path))
    assert [k for k, _ in sites] == ["m.py::bad"]


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
