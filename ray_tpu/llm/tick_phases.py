"""Phases of the serving tick: where the host's time between two decode
steps goes.

Every instant of a running `EngineReplica` belongs to exactly one LEAF
phase: the replica's decode loop and the engine's `step()` call
`TickPhases.to(<leaf>)` at each boundary, which closes the open phase and
opens the next at ONE monotonic stamp.  That one call feeds three sinks,
and books a fourth fact with the same stamp (the device's known-empty
time, below):

  counters    cumulative ns per leaf (`snapshot()`, served as
              `EngineReplica.debug_stats()["tick"]`): exact window totals
              with no ring to overflow, counted whether or not the flight
              recorder is on.  Two snapshots differ, leaf by leaf, by
              exactly the time between their stamps `t`, so the replica
              takes three a request and every reply carries where its own
              time went (`serving.py`: the terminal item's `timing`);
  spans       the flight recorder's `request` category (the names in
              `_SPAN` below; `_private/flight_recorder.py` lists them
              all), every span of one tick carrying the tick number `n`;
  annotation  a `jax.profiler.TraceAnnotation` named `ray_tpu/tick:<leaf>`
              held open for as long as the phase is, so a profile of the
              replica shows on its host plane, in the profiler's own
              timebase, what the host did in each device gap.  (A TraceMe
              costs well under a microsecond while no profile is taken,
              and is recorded whole at its exit, on the exiting thread's
              line: `hop` opens on one thread and closes on the other.)

Leaves: `idle` (nothing unfinished, waiting for a request), `turn` (tick
end -> the loop holds the replica's lock again: release, one loop turn,
the `_stream` calls that enqueue meanwhile, other holders of the lock),
`expire`, `hop` (the executor hand-off, there and back), `admit` and
`chunk` (SELF time of `step:admit` / `step:chunk`: reserve, cache lookup,
page tables), `prefill` and `sample_sync` (inside either), `prep`
(the step's bookkeeping and, when the host touched a slot since the last
step, the one packed upload of the touched rows), `dispatch`, `wait` (the blocking read-back),
`emit` (the emit/retire loops: requests finished at admission and
paged-context slots before the decode step, every slot after it),
`ahead` (a LATER step's `prep` and `dispatch`, wherever `step()` sends a
decode step off before the call that will read it: at the end of a call,
once its own step is read, so that the device runs the next one through
`hop`, `fan_out`, `turn` and the next tick's `expire`, `hop` and `admit`
(PR 38); and at the start of a call that finds a step still out, BEHIND
that step and before it is read, so that the device holds one step running
and one queued and never waits for the read-back, `emit` or the loop (PR
48: `decode_stats()["steps_queued"]`, the `decode` span's `queued=`).  The
tick that reads a step sent off earlier has an empty `prep` and
`dispatch`), `fan_out`.  Parent spans (`tick`, `step:admit`, `step:chunk`,
`decode`) are stamped by their callers from the stamps `to()` returns.

A step queued behind the one that sampled a reply's EOS still holds that
reply's row: a DEAD step for the row, whose token is dropped and which
counts in none of the `decode` span's numbers (`LLMEngine.step`).

A tick's first tokens do not wait for its decode step: once that step is
dispatched, still in `dispatch`, the engine's thread calls the replica's
hook (`EngineReplica._hand_first`), which posts `_fan_out` onto the loop;
the LOOP's thread, idle while it awaits `step()`, puts them on their
streams and takes the request's snapshot S1 there, while the open leaf is
the engine's (`dispatch` or `wait`).

One thread at a time drives this object: the loop's thread, or — while the
loop awaits `step()` — the executor's; `snapshot()` may be called from the
other one (a sequence number brackets every change of the counters, so it
reads them whole).  Engine work outside a tick
(`prefill_only`, `sample_first`: another holder of the replica's lock)
records its spans as before and counts as the loop's `turn`.

`admitting` counts the ticks that stopped the running streams for somebody
else's prompt: those in which `_admit` gave a request a slot or a chunked
prefill advanced.  The window's `STOP` nanoseconds over it are what one
admission costs a stream that is decoding.

When the chip had nothing to run.  `ns` counts a leaf's host time whether
it hid under a running step or starved the chip; `empty_ns` says which.
The device runs what one process sends it in order, so two sequence numbers
are enough: `sent()`, called after every call on the serving path that
enqueued a program (a decode step, a prefill, an install, the sampler's
eager programs; a `device_put` of rows is no program), counts them, and
`seen(number)`, called after a blocking read-back has returned, says what
`sent()` had returned when the program read was enqueued (a `_Flight`
keeps its number).  The device is KNOWN EMPTY from the stamp at which a
read-back returns with `seen == sent`, everything sent has been read, until
the return of the next call that enqueues a program; a step read while
another is queued behind it opens nothing.  `to()` books the open
interval's part of the closing leaf to `empty_ns[<leaf>]` with the stamp it
takes anyway (one branch a call); `sent()` and `seen()` take a stamp only
where they close or open an interval.  So `empty_ns[leaf] <= ns[leaf]`
between any two snapshots, `snapshot()` serves both (and the two numbers)
from one sequence bracket, and a reply's `timing` carries its own share
(`first_empty`, `rest_empty`).  A `jax.profiler.TraceAnnotation` named
`ray_tpu/device:empty` is held open over each interval, as
`ray_tpu/tick:<leaf>` is over a leaf: the program's belief on the host
plane of any profile, beside the device plane's truth.  The account is a
LOWER bound of the device's idle time, by design: a program that ended
before it was read (the read-back's late return inside `wait`, a step sent
ahead that ends while `admit` runs) left the chip idle for a stretch the
host cannot know; gaps inside a program and anything sent from outside the
serving path (a reference check's programs) are not seen at all.  A
profile's idle share is the upper bound, and the difference is what the
read-back and the launch cost.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

from jax.profiler import TraceAnnotation

from .._private import clocks, flight_recorder

LEAVES = ("idle", "turn", "expire", "hop", "admit", "prefill",
          "sample_sync", "chunk", "prep", "dispatch", "wait", "emit",
          "ahead", "fan_out")
# The leaves in which a running stream stands still for an admission.
STOP = ("admit", "prefill", "sample_sync", "chunk")

# Leaf -> the span a finished piece of it is recorded as.  The others are
# self time of a parent span, or spans their caller records with the
# request's id and its own arguments (`prefill`, `sample_sync`).
_SPAN = {"idle": "tick:idle", "turn": "tick:turn", "expire": "tick:expire",
         "hop": "tick:hop", "fan_out": "tick:fan_out",
         "prep": "decode:prep", "dispatch": "decode:dispatch",
         "wait": "decode:wait", "emit": "step:emit", "ahead": "step:ahead"}


class TickPhases:

    def __init__(self):
        self.n = 0                      # ticks begun
        self.admitting = 0              # of them, ticks that admitted
        self.ns: Dict[str, int] = dict.fromkeys(LEAVES, 0)
        # Of `ns`, the part in which the device was known empty.
        self.empty_ns: Dict[str, int] = dict.fromkeys(LEAVES, 0)
        self.in_tick = False            # a replica's loop drives this tick
        self.in_step = False            # inside LLMEngine.step()
        self._open: Tuple[Optional[str], int] = (None, 0)    # leaf, since
        # Programs enqueued, and of them how many are known to have ended
        # (read back, or enqueued before one that was).
        self._sent = 0
        self._seen = 0
        self._empty: Optional[int] = None   # known empty since; None: not
        # Odd while a stamp is taken and time moves into the counters:
        # `snapshot()` on the other thread reads between two even values,
        # its own stamp among them.
        self._seq = 0
        self._note: Optional[TraceAnnotation] = None
        self._empty_note: Optional[TraceAnnotation] = None
        self._tick: Tuple[int, int, int] = (0, 0, 0)

    # ------------------------------------------------------------ leaves --
    def to(self, leaf: Optional[str], **closing: Any) -> int:
        """Close the open phase and open `leaf` (None: nothing) at one
        stamp, which is returned.  `closing` are arguments of the span of
        the phase that closes."""
        cur, since = self._open
        self._seq += 1
        now = clocks.mono_ns()
        if cur is not None:
            self.ns[cur] += now - since
        if self._empty is not None:
            if cur is not None:
                self.empty_ns[cur] += now - self._empty
            self._empty = now
        self._open = (leaf, now)
        self._seq += 1
        if cur is not None:
            self._note.__exit__(None, None, None)
            name = _SPAN.get(cur)
            if name is not None:
                if cur != "idle":
                    closing["n"] = self.n
                flight_recorder.recorder().span_at(
                    "request", name, since, now, **closing)
        if leaf is not None:
            self._note = TraceAnnotation("ray_tpu/tick:" + leaf)
            self._note.__enter__()
        return now

    def enter(self, leaf: str) -> Tuple[int, Optional[str]]:
        """Start of work that is a leaf of its own inside `step()`
        (`prefill`, `sample_sync`) and a plain span outside it.  Returns
        the token `leave` takes."""
        if not self.in_step:
            return clocks.mono_ns(), None
        back = self._open[0]
        return self.to(leaf), back

    def leave(self, token: Tuple[int, Optional[str]], name: str,
              id: bytes = b"", **args: Any) -> None:
        """End of it: back to the phase it interrupted, and the span
        `name` over exactly its extent."""
        t0, back = token
        t1 = clocks.mono_ns() if back is None else self.to(back)
        flight_recorder.recorder().span_at("request", name, t0, t1, id,
                                           **args)

    def span(self, name: str, t0: int, t1: int, **args: Any) -> None:
        """A parent span of this tick, from stamps `to()` returned."""
        flight_recorder.recorder().span_at("request", name, t0, t1,
                                           n=self.n, **args)

    # ------------------------------------------------------------ device --
    def sent(self) -> int:
        """After a call that enqueued a program (or several): the device
        has work from here on.  Returns the count, which `seen` takes once
        the program's result has been read."""
        self._sent += 1
        if self._empty is not None:
            self._seq += 1
            cur = self._open[0]
            if cur is not None:
                self.empty_ns[cur] += clocks.mono_ns() - self._empty
            self._empty = None
            self._seq += 1
            self._empty_note.__exit__(None, None, None)
        return self._sent

    def seen(self, number: int) -> None:
        """After a blocking read of what program `number` returned: it and
        every program before it have ended.  Nothing sent since: the
        device is known empty from now on."""
        if number > self._seen:
            self._seen = number
        if self._seen == self._sent and self._empty is None:
            self._seq += 1
            self._empty = clocks.mono_ns()
            self._seq += 1
            self._empty_note = TraceAnnotation("ray_tpu/device:empty")
            self._empty_note.__enter__()

    # -------------------------------------------------------------- tick --
    def tick_begin(self, enqueued: int, active: int, waiting: int) -> None:
        """The loop holds the lock: `turn` closes under the NEW tick's
        number (a turn that ended in `idle` kept the old one)."""
        self.n += 1
        self.in_tick = True
        self._tick = (self.to("expire", enqueued=enqueued), active, waiting)

    def tick_end(self) -> None:
        t0, active, waiting = self._tick
        self.in_tick = False
        self.span("tick", t0, self.to("turn"), active=active,
                  waiting=waiting)

    # ---------------------------------------------------------- counters --
    def snapshot(self) -> Dict[str, Any]:
        """Ticks begun, those of them that admitted, and cumulative ns per
        leaf with the open phase counted up to the stamp `t`: two snapshots
        bracket a window exactly, `sum(ns)` apart by their `t`s.  Beside
        `ns`, never inside it: `empty_ns`, the part of each leaf in which
        the device was known empty (the open interval counted up to `t`
        too), and the two numbers it rests on, `sent` and `seen`."""
        while True:
            seq = self._seq
            ns = dict(self.ns)
            empty_ns = dict(self.empty_ns)
            cur, since = self._open
            empty = self._empty
            seen, sent = self._seen, self._sent
            t = clocks.mono_ns()
            if not seq % 2 and seq == self._seq:
                break
            time.sleep(0)               # let the thread inside `to()` finish
        if cur is not None:
            ns[cur] += t - since
            if empty is not None:
                empty_ns[cur] += t - empty
        return {"n": self.n, "admitting": self.admitting, "t": t, "ns": ns,
                "empty_ns": empty_ns, "sent": sent, "seen": seen}
