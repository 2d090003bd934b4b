"""Fake device behaviour for the decode engine's tests: programs whose
answers reach the host late, or when the test says so.

The engine's programs return their state and, after it, the results the
host fetches (`np.asarray`).  `hold_answers` wraps a program so that the
state passes through as it is (the next program can be launched behind
it, as on a device) while each result's fetch blocks until the program's
turn is released: what a device that is still computing looks like from
the host."""

import threading
import time

import jax
import numpy as np


class Gate:
    """Releases held answers one program at a time (`release(n)`), after
    a fixed delay each (`delay_s`), or all of them (`open()`)."""

    def __init__(self, delay_s=None):
        self.delay_s = delay_s
        self._turns = threading.Semaphore(0)
        self._open = threading.Event()
        self.held = 0                     # programs launched through it

    def release(self, n=1):
        for _ in range(n):
            self._turns.release()

    def open(self):
        self._open.set()
        self.release(1000)

    def wait(self):
        if self.delay_s is not None:
            time.sleep(self.delay_s)
        elif not self._open.is_set():
            self._turns.acquire()


class _Held:
    """A program's result whose readiness and fetch wait for the gate
    (the first result of a program waits for all of them)."""

    def __init__(self, value, turn):
        self._value, self._turn = value, turn

    def block_until_ready(self):
        self._turn()
        return self

    def __array__(self, dtype=None, copy=None):
        self._turn()
        out = np.asarray(self._value)
        return out if dtype is None else out.astype(dtype)


def hold_answers(fn, gate):
    """`fn` (one of the engine's programs) with its answers held by
    `gate`."""
    def program(*args):
        state, *results = fn(*args)
        gate.held += 1
        once = threading.Lock()
        waited = []

        def turn():
            with once:
                if not waited:
                    gate.wait()
                    waited.append(True)

        # leaf by leaf: the last result is the model's dict of counters
        return (state, *jax.tree.map(lambda r: _Held(r, turn), results))

    return program


def hold_steps(eng, gate):
    """Hold the answers of `eng`'s decode steps."""
    eng._step_fn = hold_answers(eng._step_fn, gate)


def hold_prefills(eng, gate):
    """Hold the answers of `eng`'s prefills."""
    eng._prefill_fns = {b: hold_answers(fn, gate)
                        for b, fn in eng._prefill_fns.items()}


def until(cond, timeout=30.0, what="condition"):
    """Poll `cond()` until it holds."""
    end = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > end:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.001)
