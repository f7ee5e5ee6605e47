"""Device time of a program's own scopes (``jax.named_scope``), from the
traced window: the SELF time of each ``XLA Ops`` event (its duration less the
events nested in it on the same line: a ``while`` holds its body's
operations) is booked to the scope path of its instruction, which the program
reads back from its compiled module (``multiverso_tpu.telemetry.
program_scopes``: module name -> instruction name -> ``op_name`` path), and
summed over the runs of the programs matching ``module`` that lie wholly
inside the window.

A scope is matched as a whole path component, the wrappers jax puts around
one peeled off (``transpose(jvp(lm_mla))`` is ``lm_mla``: the program's own
``scope_names``). ``scope``: the regular expression of the scopes to sum.
``unscoped``: sum instead what lies under NONE of these scopes (an
instruction the map lacks included), and ``scope`` only has to be there.
``per``: ``run`` (milliseconds a program run) or ``share`` (percent of the
programs' device time). ``devices``: one device's number, or ``all`` for the
busiest device's over the mean.

Returns None, and prints a line saying why, where the program has no such
map (it predates the registry) or its executable holds no instruction under
``scope``: an executable the compile cache kept from before the scope was
written has the old names, in the trace and in its text alike.

Printed beside the numbers, once a trace and program: its largest
instructions by self time with their paths (and the share of its device time
whose instruction the map knows), the largest outside every scope where
``unscoped`` is read, each device's number under ``all``, and what
``program_scopes()`` took where it took long.
"""
import bisect
import json
import re
import time
import weakref

import trace_reduce

_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
_tables = weakref.WeakKeyDictionary()   # Trace -> {device: (starts, rows)}
_reported = weakref.WeakKeyDictionary()  # Trace -> listings already printed
TOP = 12


def self_times(events) -> list:
    """``[start, self ns, instruction name]`` of one line's events ``(start,
    end, HLO text)``, by start."""
    rows, open_ = [], []
    for a, b, text in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_ and open_[-1][0] <= a:
            open_.pop()
        if open_:
            end, parent = open_[-1]
            parent[1] -= min(b, end) - a
        row = [a, b - a, _INSTRUCTION.match(text.strip()).group(1)]
        rows.append(row)
        open_.append((b, row))
    return rows


def _table(tr, device):
    per_device = _tables.setdefault(tr, {})
    if device not in per_device:
        rows = self_times(tr.ops.get(device, []))
        per_device[device] = ([r[0] for r in rows], rows)
    return per_device[device]


def _say(why: str) -> None:
    print("scope_device: " + why, flush=True)


def _device_scopes():
    """The program's registry module; None from a program without one."""
    try:
        from multiverso_tpu.telemetry import device_scopes
    except ImportError:
        _say("this program keeps no map from instruction to scope")
        return None
    return device_scopes


def _one_device(tr, win, device, module_rx, maps, hit, by_instruction=None,
                only_hits=False):
    """(ns under ``hit``, ns of the programs, runs) on one device, None
    where no program ran whole; ``hit`` answers once a path. Given a dict,
    it is filled with ``{instruction: [ns, path]}``, of every instruction
    or of the hits alone."""
    runs = [(a, b, trace_reduce.module_name(name))
            for a, b, name in tr.modules.get(device, [])
            if a >= win[0] and b <= win[1]
            and module_rx.search(trace_reduce.module_name(name))]
    if not runs:
        return None
    starts, rows = _table(tr, device)
    found = whole = 0.0
    hits = {}
    for a, b, name in runs:
        whole += b - a
        paths = maps.get(name, {})
        for _, ns, instruction in rows[bisect.bisect_left(starts, a):
                                       bisect.bisect_left(starts, b)]:
            path = paths.get(instruction, "")
            if path not in hits:
                hits[path] = hit(path)
            if hits[path]:
                found += ns
            if by_instruction is not None and (hits[path] or not only_hits):
                by_instruction.setdefault(instruction, [0.0, path])[0] += ns
    return found, whole, len(runs)


def _say_largest(module, listing, whole, runs, outside):
    """Once a trace and program: its largest instructions by self time, or
    the largest of those outside every scope."""
    top = sorted(listing.items(), key=lambda kv: -kv[1][0])[:TOP]
    mapped = sum(ns for ns, path in listing.values() if path)
    what = ("outside every scope: " if outside else
            f"{100 * mapped / whole:.2f}% of it under instructions the map "
            "knows; ")
    _say(f"largest of {module} ({runs} runs of {whole / runs / 1e6:.3f} ms, "
         f"{what}ms a run, path) " + json.dumps(
             [[k, round(ns / runs / 1e6, 3), path]
              for k, (ns, path) in top]))


def read(ctx, module, scope, per="run", unscoped=None, devices=0):
    tr, win = ctx.trace_data, ctx.trace_window
    on = None if tr is None else (
        tr.devices() if devices == "all" else [int(devices)])
    if win is None or not on or not set(on) <= set(tr.devices()):
        return None
    registry = _device_scopes()
    if registry is None:
        return None
    t0 = time.perf_counter()
    every = registry.program_scopes()
    took = time.perf_counter() - t0
    if took > 0.1:      # the first ask lowers every program and fetches it
        _say(f"program_scopes() took {took:.2f} s for {sorted(every)}")
    scope_names = registry.scope_names
    module_rx, scope_rx = re.compile(module), re.compile(scope)
    maps = {name: paths for name, paths in every.items()
            if module_rx.search(name)}
    if not maps:
        _say(f"no registered program matches {module!r}")
        return None
    if not any(scope_rx.fullmatch(n) for paths in maps.values()
               for path in paths.values() for n in scope_names(path)):
        _say(f"no instruction of {sorted(maps)} lies under {scope!r}: an "
             "executable compiled before the scope was written?")
        return None
    if unscoped is not None:
        none_of = re.compile(unscoped)

        def hit(path):
            return not any(none_of.fullmatch(n) for n in scope_names(path))
    else:
        def hit(path):
            return any(scope_rx.fullmatch(n) for n in scope_names(path))

    values = {}
    listed = (module, unscoped is not None)
    reported = _reported.setdefault(tr, set())
    for device in on:
        listing = {} if device == 0 and listed not in reported else None
        got = _one_device(tr, win, device, module_rx, maps, hit, listing,
                          only_hits=unscoped is not None)
        if got is None:
            continue
        found, whole, runs = got
        values[device] = (100.0 * found / whole if per == "share"
                          else found / runs / 1e6)
        if listing is not None:
            reported.add(listed)
            _say_largest(module, listing, whole, runs, unscoped is not None)
    if not values:
        return None
    if devices != "all":
        return values[int(devices)]
    _say(f"{unscoped and 'outside ' + unscoped or scope} of {module} on "
         f"each device: {json.dumps(values)}")
    mean = sum(values.values()) / len(values)
    return max(values.values()) / mean if mean > 0 else None
