"""Runtime lock-order deadlock detector (opt-in, test-time).

Static lints catch single-class discipline; deadlocks live in the spaces
BETWEEN components (a gossip receiver holding `_peers_lock` calling into a
PeerDB that a heartbeat thread is traversing the other way). This module
patches `threading.Lock`/`threading.RLock` with instrumented wrappers that
record, per thread, which locks are held when another is acquired. Every
(held -> acquired) pair becomes an edge in a process-global lock-order
graph, stamped with the acquiring thread's stack. Two violation kinds:

  lock-order-cycle     adding an edge closes a cycle in the order graph —
                       two threads CAN interleave into a deadlock, even if
                       this run got lucky. The report carries the
                       acquisition stack of every edge on the cycle (i.e.
                       both sides of an AB/BA inversion).
  dispatch-under-lock  a device dispatch (`verify_signature_sets*`) ran
                       while the calling thread held an instrumented lock.
                       Device calls block for milliseconds or more —
                       holding a lock across one turns every contender
                       into a convoy.

Activation: `conftest.py` installs a fresh detector per test for the
concurrency/batch-verifier/gossip modules when LIGHTHOUSE_TPU_LOCKCHECK=1,
and fails the test on any violation. Only locks CREATED while installed
are instrumented (import-time module locks are not, deliberately — they
predate the patch and belong to infrastructure like the metrics registry).

The wrappers stay safe under `queue.Queue`/`threading.Condition`: they
expose acquire/release/locked and the RLock internals Condition probes,
and a detector that has been uninstalled goes inert without breaking
wrappers that outlive it.
"""

from __future__ import annotations

import _thread
import sys
import threading
import traceback
from dataclasses import dataclass, field

#: backend modules whose dispatch entry points are wrapped when installed
DISPATCH_MODULES = (
    "lighthouse_tpu.crypto.bls.jax_backend.api",
    "lighthouse_tpu.crypto.bls.ref.api",
    "lighthouse_tpu.crypto.bls.fake",
)
DISPATCH_FNS = ("verify_signature_sets", "verify_signature_sets_async")

def _is_machinery_frame(filename: str) -> bool:
    import os.path

    return filename == __file__ or os.path.basename(filename) == "threading.py"


def _creation_site() -> str:
    """file:line of the frame that called threading.Lock() — the lock's name."""
    for f in reversed(traceback.extract_stack()):
        if not _is_machinery_frame(f.filename):
            return f"{f.filename}:{f.lineno}"
    return "<unknown>"


def _current_stack() -> str:
    """Formatted stack of the caller, trimmed of lockcheck/threading frames."""
    frames = [
        f for f in traceback.extract_stack()[:-2] if not _is_machinery_frame(f.filename)
    ]
    return "".join(traceback.format_list(frames[-12:]))


@dataclass
class Edge:
    """First-seen (held -> acquired) ordering, with the acquiring stack."""

    frm: str  # held lock name
    to: str  # acquired lock name
    thread: str
    stack: str


@dataclass
class Violation:
    kind: str  # "lock-order-cycle" | "dispatch-under-lock"
    description: str
    stacks: list[tuple[str, str]] = field(default_factory=list)  # (label, stack)

    def format(self) -> str:
        out = [f"[{self.kind}] {self.description}"]
        for label, stack in self.stacks:
            out.append(f"--- {label} ---")
            out.append(stack.rstrip())
        return "\n".join(out)


class _Held:
    __slots__ = ("lock", "count")

    def __init__(self, lock):
        self.lock = lock
        self.count = 1


class Detector:
    """The order graph + violation log. One per install()."""

    def __init__(self):
        self.active = True
        self.violations: list[Violation] = []
        self._graph_lock = _thread.allocate_lock()  # raw: never instrumented
        self._edges: dict[tuple[int, int], Edge] = {}
        self._adj: dict[int, set[int]] = {}
        self._names: dict[int, str] = {}
        self._tls = threading.local()

    # -- held-stack bookkeeping (per thread) -----------------------------------

    def _held(self) -> list[_Held]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def on_acquired(self, lock: "InstrumentedLock") -> None:
        held = self._held()
        for h in held:
            if h.lock is lock:  # RLock re-entry: no new ordering
                h.count += 1
                return
        if held and self.active:
            self._record_edges(held, lock)
        held.append(_Held(lock))

    def on_released(self, lock: "InstrumentedLock") -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i].lock is lock:
                held[i].count -= 1
                if held[i].count == 0:
                    del held[i]
                return

    def holding(self) -> list[str]:
        return [h.lock.name for h in self._held()]

    # -- order graph -----------------------------------------------------------

    def _record_edges(self, held: list[_Held], lock) -> None:
        stack = None
        with self._graph_lock:
            self._names[id(lock)] = lock.name
            for h in held:
                self._names[id(h.lock)] = h.lock.name
                key = (id(h.lock), id(lock))
                if key in self._edges:
                    continue
                if stack is None:
                    stack = _current_stack()
                edge = Edge(
                    frm=h.lock.name,
                    to=lock.name,
                    thread=threading.current_thread().name,
                    stack=stack,
                )
                self._edges[key] = edge
                self._adj.setdefault(key[0], set()).add(key[1])
                path = self._find_path(key[1], key[0])
                if path is not None:
                    self._report_cycle(edge, key, path)

    def _find_path(self, src: int, dst: int) -> list[tuple[int, int]] | None:
        """Edge-path src -> ... -> dst in the order graph (DFS), or None."""
        stack = [(src, [])]
        seen = {src}
        while stack:
            node, path = stack.pop()
            if node == dst:
                return path
            for nxt in self._adj.get(node, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append((nxt, path + [(node, nxt)]))
        # dst may equal src only via an edge loop; handle src==dst upfront
        return [] if src == dst else None

    def _report_cycle(self, new_edge: Edge, new_key, path) -> None:
        cycle_edges = [self._edges[k] for k in path] + [new_edge]
        order = " -> ".join([new_edge.frm, new_edge.to] + [self._names[k[1]] for k in path])
        v = Violation(
            kind="lock-order-cycle",
            description=(
                f"lock acquisition order cycle: {order} (potential deadlock; "
                f"{len(cycle_edges)} conflicting orderings observed)"
            ),
            stacks=[
                (
                    f"thread {e.thread!r} acquired {e.to!r} while holding {e.frm!r}",
                    e.stack,
                )
                for e in cycle_edges
            ],
        )
        self.violations.append(v)

    # -- device dispatch -------------------------------------------------------

    def note_dispatch(self, label: str) -> None:
        if not self.active:
            return
        holding = self.holding()
        if holding:
            self.violations.append(
                Violation(
                    kind="dispatch-under-lock",
                    description=(
                        f"device dispatch {label} while holding {holding}: a "
                        f"multi-ms device call under a lock convoys every "
                        f"contender"
                    ),
                    stacks=[("dispatching thread", _current_stack())],
                )
            )


class InstrumentedLock:
    """Drop-in threading.Lock/RLock stand-in that reports to a Detector."""

    def __init__(self, detector: Detector, inner, name: str):
        self._detector = detector
        self._inner = inner
        self.name = name

    def acquire(self, blocking=True, timeout=-1):
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._detector.on_acquired(self)
        return ok

    def release(self):
        self._inner.release()
        self._detector.on_released(self)

    def locked(self):
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):  # pragma: no cover — debugging aid
        return f"<InstrumentedLock {self.name}>"


class InstrumentedRLock(InstrumentedLock):
    """RLock variant; exposes the internals threading.Condition probes."""

    def locked(self):  # RLock has no .locked() before 3.12; mirror _is_owned
        return self._inner._is_owned()

    def _is_owned(self):
        return self._inner._is_owned()

    def _release_save(self):
        state = self._inner._release_save()
        # a full release (Condition.wait): clear this thread's held entry
        held = self._detector._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i].lock is self:
                del held[i]
                break
        return state

    def _acquire_restore(self, state):
        self._inner._acquire_restore(state)
        self._detector.on_acquired(self)


# -- install / uninstall -------------------------------------------------------

#: the genuine factories, captured at import time (before any patching)
_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock

_installed: Detector | None = None
_saved: dict = {}


def _patched_lock():
    """Stable stand-in for threading.Lock. Consults the CURRENTLY installed
    detector at call time, so a reference captured while patched (e.g. a
    dataclass `field(default_factory=threading.Lock)` evaluated during an
    instrumented test) keeps working after uninstall — and instruments for
    the new detector on the next install."""
    det = _installed
    if det is None:
        return _REAL_LOCK()
    return InstrumentedLock(det, _REAL_LOCK(), _creation_site())


def _patched_rlock():
    det = _installed
    if det is None:
        return _REAL_RLOCK()
    return InstrumentedRLock(det, _REAL_RLOCK(), _creation_site())


def install() -> Detector:
    """Patch threading.Lock/RLock (and the BLS dispatch entry points of any
    imported backend) so locks created from now on are instrumented.
    Returns the live Detector; pair with uninstall()."""
    global _installed
    if _installed is not None:
        raise RuntimeError("lockcheck already installed")
    det = Detector()
    threading.Lock = _patched_lock
    threading.RLock = _patched_rlock
    _installed = det

    for modname in DISPATCH_MODULES:
        mod = sys.modules.get(modname)
        if mod is None:
            continue
        for fnname in DISPATCH_FNS:
            orig = getattr(mod, fnname, None)
            if orig is None or getattr(orig, "__lockcheck_wrapped__", False):
                continue
            _saved[(modname, fnname)] = orig

            def wrapper(*args, __orig=orig, __label=f"{modname}.{fnname}", **kwargs):
                det.note_dispatch(__label)
                return __orig(*args, **kwargs)

            wrapper.__lockcheck_wrapped__ = True
            setattr(mod, fnname, wrapper)

    return det


def uninstall() -> list[Violation]:
    """Restore threading + dispatch functions; returns the violations.
    Wrappers created while installed keep working (detector goes inert)."""
    global _installed
    det = _installed
    if det is None:
        return []
    det.active = False
    threading.Lock = _REAL_LOCK
    threading.RLock = _REAL_RLOCK
    for key in [k for k in _saved if isinstance(k, tuple)]:
        modname, fnname = key
        mod = sys.modules.get(modname)
        if mod is not None:
            setattr(mod, fnname, _saved[key])
        del _saved[key]
    _installed = None
    return det.violations


def format_report(violations) -> str:
    return "\n\n".join(v.format() for v in violations) or "no lockcheck violations"
