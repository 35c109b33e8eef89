"""The client: one thread that sends, one callback that records.

Everything the window touches is allocated in set-up. The sender records one
stamp pair per block of sends into arrays sized before the window; the
callback writes an arrival stamp and the row's values into arrays sized from
the mix's ``max_rate_eps``. No list grows while the window is open.
"""

from __future__ import annotations

import time

import numpy as np

_pc = time.perf_counter


class Egress:
    """Arrival stamps and row values, by row, in arrival order."""

    def __init__(self, out_columns: list, capacity: int):
        self.names = [name for name, _ in out_columns]
        self.capacity = int(capacity)
        self.stamp = np.zeros(self.capacity, dtype=np.float64)
        self.cols = [np.zeros(self.capacity,
                              dtype=np.int64 if kind == "int" else np.float64)
                     for _, kind in out_columns]
        # [rows stored, rows that found the buffers full]
        self.state = [0, 0]

    @property
    def n(self) -> int:
        return self.state[0]

    @property
    def overflowed(self) -> int:
        return self.state[1]

    def columns(self) -> dict:
        return dict(zip(self.names, self.cols))

    def callback(self):
        """``fn(events)`` for a ``StreamCallback``. Unrolled over the row's
        columns: it runs once per row on the thread that publishes rows, so
        what it costs is taken from the system under test."""
        width = len(self.cols)
        stores = "; ".join(f"_b{j}[k] = d[{j}]" for j in range(width))
        src = (
            "def cb(evs):\n"
            "    k = _st[0]\n"
            "    for e in evs:\n"
            "        if k >= _cap:\n"
            "            _st[1] += 1\n"
            "            continue\n"
            "        d = e.data\n"
            "        _stamp[k] = _pc()\n"
            f"        {stores}\n"
            "        k += 1\n"
            "    _st[0] = k\n")
        scope = {"_st": self.state, "_cap": self.capacity, "_pc": _pc,
                 "_stamp": memoryview(self.stamp)}
        for j, col in enumerate(self.cols):
            scope[f"_b{j}"] = memoryview(col)
        exec(src, scope)    # noqa: S102 — text built above from a column count
        return scope["cb"]


class Blocks:
    """One record per block of sends: first event, count, clock before and
    after. Ingress cost, generator lateness and the fill wait are read from
    these after the window."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self.first = np.zeros(self.capacity, dtype=np.int64)
        self.count = np.zeros(self.capacity, dtype=np.int64)
        self.t0 = np.zeros(self.capacity, dtype=np.float64)
        self.t1 = np.zeros(self.capacity, dtype=np.float64)
        # events sent and not yet stepped when the block began
        self.outstanding = np.zeros(self.capacity, dtype=np.int64)
        self.n = 0

    def views(self):
        return (memoryview(self.first), memoryview(self.count),
                memoryview(self.t0), memoryview(self.t1),
                memoryview(self.outstanding))


def row_sender(input_handler, pool: dict, names: list, base_ts: int):
    """Per-event ``InputHandler.send``: rows are built as Python lists here,
    in set-up, and the pool is walked round."""
    rows = [list(r) for r in zip(*(pool[k].tolist() for k in names))]
    size = len(rows)
    send = input_handler.send

    def send_block(i: int, n: int) -> None:
        ts = base_ts + i
        j = i % size
        if j + n <= size:
            block = rows[j:j + n]
        else:
            block = rows[j:] + rows[:j + n - size]
        for row in block:
            send(row, timestamp=ts)
            ts += 1

    return send_block


def column_sender(input_handler, pool: dict, names: list, base_ts: int):
    """Columnar ``InputHandler.send_columns``: a block is one chunk, sliced
    from the pool's arrays (a chunk never straddles the pool's end: the
    pool's size is a multiple of every chunk size used)."""
    size = len(pool[names[0]])
    send_columns = input_handler.send_columns
    arange = np.arange

    def send_block(i: int, n: int) -> None:
        j = i % size
        if j + n > size:
            raise ValueError(f"chunk of {n} at {j} straddles the pool's end "
                             f"({size})")
        send_columns({k: pool[k][j:j + n] for k in names},
                     arange(base_ts + i, base_ts + i + n, dtype=np.int64))

    return send_block


def drive(send_block, blocks: Blocks, *, seconds: float, warm_seconds: float,
          warm_events: int, block: int, rate: float = 0.0,
          chunked: bool = False, tick_s: float = 0.0005,
          catchup: float = 0.0,
          tail_batch: int = 0, i0: int = 0, outstanding: int = 0,
          stepped=None, trace_at: float | None = None,
          on_mark=None, annotate=None) -> dict:
    """Send through ``send_block(i, n)`` without a pause from the warm
    stretch to the window's close and return the marks ``{name: (clock,
    events sent)}``.

    The first event sent is ``i0`` (what set-up sent comes before it). The
    window opens once ``warm_seconds`` have passed and ``warm_events`` were
    sent, and stays open ``seconds``.

    Closed loop (``rate`` 0): blocks of ``block`` events as fast as sends
    return while fewer than ``outstanding`` events are sent and not yet
    stepped (``stepped()``: the program's count of events stepped; 0 = no
    such limit), else a sleep of ``tick_s``.

    Open loop: event ``i`` is due at ``t_start + (i - i0) / rate``; every
    ``tick_s`` the events that have come due are sent, at most ``block`` to a
    call (``chunked``: only whole blocks, each when its last row is due). A
    generator that fell behind (a stall of the host) catches up at no more
    than ``catchup`` times the rate (0 = as fast as sends return): the events
    are late by their due times either way, and a burst at the client's full
    speed is another traffic mix. After the close an open loop goes on at its
    rate until the batch of ``tail_batch`` events that holds the window's
    last event has sealed and one more has followed it, so that the window's
    last rows arrive as they would mid-stream.

    ``on_mark(name)`` is called at the block boundary where ``open``,
    ``trace_on`` (``trace_at`` seconds after ``open``) and ``close`` fall;
    ``annotate(name)`` wraps each block of sends in a profiler annotation
    (traced runs only).
    """
    first, count, bt0, bt1, backlog = blocks.views()
    cap = blocks.capacity
    marks: dict = {}
    i = i0
    nb = i_stop = 0
    not_before = 0.0
    phase = "warm"
    sleep = time.sleep
    t_start = t_open = _pc()
    marks["start"] = (t_start, i0)

    def mark(name, now):
        marks[name] = (now, i)
        if on_mark is not None:
            on_mark(name)

    while True:
        now = _pc()
        if phase == "warm":
            if now - t_start >= warm_seconds and i - i0 >= warm_events:
                t_open = now
                mark("open", now)
                phase = "window"
        elif phase == "window":
            if trace_at is not None and "trace_on" not in marks \
                    and now - t_open >= trace_at:
                mark("trace_on", now)
            if now - t_open >= seconds:
                mark("close", now)
                if not rate:
                    break
                phase = "tail"
                i_stop = (i // tail_batch + 2) * tail_batch
        elif i >= i_stop:
            break
        behind = i - stepped() if stepped is not None else 0
        if rate:
            due = int((now - t_start) * rate) + 1 - (i - i0)
            n = block if due >= block else (0 if chunked else due)
            if n <= 0 or now < not_before:
                sleep(tick_s)
                continue
            if catchup:
                not_before = now + n / (rate * catchup)
        else:
            if outstanding and behind >= outstanding:
                sleep(tick_s)
                continue
            n = block
        if nb >= cap:
            marks["blocks_overflow"] = (now, i)
            break
        if annotate is not None:
            with annotate("bench:send"):
                t0 = _pc()
                send_block(i, n)
                t1 = _pc()
        else:
            t0 = _pc()
            send_block(i, n)
            t1 = _pc()
        first[nb] = i
        backlog[nb] = behind
        count[nb] = n
        bt0[nb] = t0
        bt1[nb] = t1
        nb += 1
        i += n
    blocks.n = nb
    marks["stop"] = (_pc(), i)
    return marks
