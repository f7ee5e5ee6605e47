"""Runtime lock witness: acquisition-order ledger, hold times, postmortems.

The static half of graftsan (``analysis/interproc.py``) proves what the
acquisition graph *could* do; this module watches what it actually
*does*.  Locks built through ``utils/locks.make_lock(name)`` while the
witness is enabled record, at near-zero cost per acquisition:

* **acquisition-order pairs** — for every lock acquired while others are
  held by the same thread, one ``held -> acquired`` edge per held lock
  goes into the process-global ledger (name pair, count, thread names).
  Merged across threads — and across processes via :func:`ledger` /
  :func:`merge_ledgers` — the edges form the observed lock-order graph;
  a cycle in it is a *witnessed* deadlock recipe, and
  :func:`check_inversions` trips a postmortem on one.
* **hold-time histograms** — ``lock.<name>.held_ms`` per named lock
  (the metric catalog's ``lock.*`` family): a convoy shows up as a
  fat tail here long before it shows up as a throughput regression.
* **blocking-while-held events** — a thread that waited more than
  :data:`BLOCKED_WHILE_HELD_MS` for a lock *while already holding
  others* is the convoy shape that cost PR 15 26% add throughput; each
  occurrence lands in the flight recorder with the held set.

The cross-check is the point (tests/test_lock_witness.py): every
cross-module edge the static analysis claims must either be OBSERVED
live by this witness under a representative scenario or carry a
reasoned suppression — a static claim reality never exercises is a
finding too.

Everything here uses *plain* ``threading`` primitives internally (the
witness must never witness itself), and nothing imports jax.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from multiverso_tpu.telemetry.metrics import counter, histogram

__all__ = ["WitnessLock", "WitnessRLock", "WitnessCondition",
           "wrap_lock", "wrap_rlock", "wrap_condition",
           "observed_edges", "observed_locks", "ledger", "merge_ledgers",
           "find_cycles", "check_inversions", "reset_lockwitness",
           "BLOCKED_WHILE_HELD_MS", "LEDGER_SCHEMA"]

LEDGER_SCHEMA = "multiverso_tpu.telemetry.lock_ledger/v1"

#: A thread that waits longer than this for a lock while holding others
#: is convoying someone: note it in the flight recorder. 5ms ~= one
#: fsync — exactly the PR-15 shape.
BLOCKED_WHILE_HELD_MS = 5.0

# -- process-global ledger state --------------------------------------------
#: Guards _edges/_locks/_hists. A LEAF by decree: nothing is ever
#: acquired under it, and it is a plain Lock so the witness never
#: witnesses itself.
_state_lock = threading.Lock()
_edges: Dict[Tuple[str, str], Dict] = {}
_locks: Dict[str, str] = {}                  # name -> kind
_hists: Dict[str, object] = {}               # name -> held_ms Histogram
_tl = threading.local()                      # per-thread held stack


def _held_stack() -> List[list]:
    held = getattr(_tl, "held", None)
    if held is None:
        held = _tl.held = []
    return held


def _register(name: str, kind: str) -> None:
    with _state_lock:
        _locks.setdefault(name, kind)


def _hist(name: str):
    h = _hists.get(name)
    if h is None:
        with _state_lock:
            h = _hists.get(name)
            if h is None:
                # Names come from the bounded make_lock seam (string
                # literals, one per lock site), never request values.
                # graftlint: disable=unbounded-metric-name
                h = _hists[name] = histogram(f"lock.{name}.held_ms")
    return h


def _note_acquired(name: str, waited_s: float, reentrant: bool,
                   order: Optional[int] = None) -> None:
    held = _held_stack()
    if reentrant:
        for entry in held:
            if entry[0] == name:
                entry[2] += 1        # re-acquire by owner: no edge
                return
    if held:
        if waited_s * 1e3 >= BLOCKED_WHILE_HELD_MS:
            counter("lock.blocked_while_held").inc()
            from multiverso_tpu.telemetry.flight import flight_recorder
            flight_recorder().note(
                "lock_blocked_while_held", lock=name,
                held=[e[0] for e in held],
                waited_ms=round(waited_s * 1e3, 3),
                thread=threading.current_thread().name)
        tname = threading.current_thread().name
        with _state_lock:
            for entry in held:
                # An ordered family: instances of ONE name taken in
                # strictly ascending order keys cannot form a cycle among
                # themselves (the classic total-order discipline), so the
                # nesting records no self-edge. Any other same-name
                # nesting (no keys, or not ascending) still does.
                if (entry[0] == name and order is not None
                        and entry[3] is not None and entry[3] < order):
                    continue
                rec = _edges.get((entry[0], name))
                if rec is None:
                    rec = _edges[(entry[0], name)] = {
                        "count": 0, "threads": set()}
                rec["count"] += 1
                rec["threads"].add(tname)
    held.append([name, time.monotonic(), 1, order])


def _note_released(name: str, full: bool = False) -> None:
    held = getattr(_tl, "held", None)
    if not held:
        return
    for i in range(len(held) - 1, -1, -1):
        if held[i][0] == name:
            held[i][2] -= 1
            if full or held[i][2] <= 0:
                hold_ms = (time.monotonic() - held[i][1]) * 1e3
                del held[i]
                _hist(name).observe(hold_ms)
            return


# -- instrumented primitives -------------------------------------------------
class WitnessLock:
    """Named non-reentrant mutex: acquisition edges + hold times.
    ``order`` (``utils.locks.set_lock_order``) is this instance's key in
    an ordered family of same-named locks."""

    _reentrant = False
    order: Optional[int] = None

    def __init__(self, name: str, inner=None):
        self.name = str(name)
        self._inner = inner if inner is not None else threading.Lock()
        _register(self.name, "rlock" if self._reentrant else "lock")

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        t0 = time.monotonic()
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            _note_acquired(self.name, time.monotonic() - t0,
                           self._reentrant, self.order)
        return ok

    def release(self) -> None:
        _note_released(self.name)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> "WitnessLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r}>"


class WitnessRLock(WitnessLock):
    """Named re-entrant mutex. Owner re-acquisition records NO edge (it
    cannot deadlock); the Condition integration hooks
    (``_release_save``/``_acquire_restore``/``_is_owned``) keep the
    witness's held-stack exact across a ``cv.wait()`` full release."""

    _reentrant = True

    def __init__(self, name: str):
        super().__init__(name, threading.RLock())

    def locked(self) -> bool:   # RLock has no .locked() pre-3.12
        if self._inner.acquire(blocking=False):
            self._inner.release()
            return False
        return True

    # -- threading.Condition protocol ---------------------------------------
    def _release_save(self):
        _note_released(self.name, full=True)
        return self._inner._release_save()

    def _acquire_restore(self, state) -> None:
        t0 = time.monotonic()
        self._inner._acquire_restore(state)
        _note_acquired(self.name, time.monotonic() - t0, False)

    def _is_owned(self) -> bool:
        return self._inner._is_owned()


class WitnessCondition(threading.Condition):
    """Named condition variable over a witnessed lock (default: a
    :class:`WitnessRLock` named after it, matching ``threading``'s
    default). ``wait`` releases through the witnessed lock, so hold
    times and edges stay exact across the park; the wait itself lands
    in ``lock.<name>.wait_ms``."""

    def __init__(self, name: str, lock=None):
        self.name = str(name)
        _register(self.name, "condition")
        super().__init__(lock if lock is not None
                         else WitnessRLock(name))

    def wait(self, timeout: Optional[float] = None) -> bool:
        t0 = time.monotonic()
        try:
            return super().wait(timeout)
        finally:
            # Bounded family: one name per make_condition literal.
            # graftlint: disable=unbounded-metric-name
            histogram(f"lock.{self.name}.wait_ms").observe(
                (time.monotonic() - t0) * 1e3)


def wrap_lock(name: str) -> WitnessLock:
    return WitnessLock(name)


def wrap_rlock(name: str) -> WitnessRLock:
    return WitnessRLock(name)


def wrap_condition(name: str, lock=None) -> WitnessCondition:
    return WitnessCondition(name, lock)


# -- ledger + checker --------------------------------------------------------
def observed_edges() -> Dict[Tuple[str, str], int]:
    """Merged ``held -> acquired`` pairs observed so far (all threads)."""
    with _state_lock:
        return {pair: rec["count"] for pair, rec in _edges.items()}


def observed_locks() -> Dict[str, str]:
    with _state_lock:
        return dict(_locks)


def ledger() -> Dict:
    """JSON-able snapshot — what a multi-process scenario ships back to
    the checker (and what the postmortem embeds)."""
    with _state_lock:
        edges = [{"src": s, "dst": d, "count": rec["count"],
                  "threads": sorted(rec["threads"])}
                 for (s, d), rec in sorted(_edges.items())]
        locks = dict(_locks)
    return {"schema": LEDGER_SCHEMA, "locks": locks, "edges": edges}


def merge_ledgers(ledgers: Iterable[Dict]) -> Dict[Tuple[str, str], int]:
    """Fold per-process ledgers into one edge map — the cross-process
    half of the checker (each serving/fleet process witnesses only its
    own threads; inversions may only exist in the union)."""
    merged: Dict[Tuple[str, str], int] = {}
    for led in ledgers:
        for e in led.get("edges", []):
            key = (str(e["src"]), str(e["dst"]))
            merged[key] = merged.get(key, 0) + int(e.get("count", 1))
    return merged


def find_cycles(edges: Iterable[Tuple[str, str]]) -> List[Tuple[str, ...]]:
    """Self-loops + one representative cycle per non-trivial SCC over
    the observed edge set (same verdict shape as the static rule)."""
    graph: Dict[str, set] = {}
    for (src, dst) in edges:
        graph.setdefault(src, set()).add(dst)
        graph.setdefault(dst, set())
    out: List[Tuple[str, ...]] = []
    for n, outs in sorted(graph.items()):
        if n in outs:
            out.append((n,))
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: set = set()
    stack: List[str] = []
    counters = [0]

    def strongconnect(v: str) -> None:
        work = [(v, iter(sorted(graph.get(v, ()))))]
        index[v] = low[v] = counters[0]
        counters[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counters[0]
                    counters[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(graph.get(w, ())))))
                    advanced = True
                    break
                elif w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                if len(scc) > 1:
                    out.append(tuple(sorted(scc)))

    for n in sorted(graph):
        if n not in index:
            strongconnect(n)
    return out


def check_inversions(edges: Optional[Dict[Tuple[str, str], int]] = None,
                     postmortem: bool = True) -> List[Tuple[str, ...]]:
    """Audit the (merged) observed edge set for lock-order cycles.
    Any cycle is a witnessed deadlock recipe: counted
    (``lock.inversions``), noted in the flight ring, and — unless the
    caller opts out — dumped as a postmortem so the all-thread stacks
    land next to the verdict."""
    if edges is None:
        edges = observed_edges()
    cycles = find_cycles(edges.keys())
    if cycles:
        counter("lock.inversions").inc(len(cycles))
        from multiverso_tpu.telemetry.flight import (dump_postmortem,
                                                     flight_recorder)
        flight_recorder().note(
            "lock_order_inversion",
            cycles=[" -> ".join(c + (c[0],)) for c in cycles])
        if postmortem:
            dump_postmortem({"kind": "lock_inversion",
                             "cycles": [list(c) for c in cycles]})
    return cycles


def reset_lockwitness() -> None:
    """Test isolation (wired into ``reset_telemetry``). Per-thread held
    stacks are left alone — live threads mid-critical-section keep
    their bookkeeping; dead threads' stacks die with their locals."""
    with _state_lock:
        _edges.clear()
        _locks.clear()
        _hists.clear()
