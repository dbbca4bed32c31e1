"""Open-loop HTTP/SSE load generator: one asyncio loop on one thread.

Each request is sent when it is due on the schedule, whatever the server
is doing, over its own connection to ``POST /v1/completions`` with
``"stream": true``. For every request the client keeps the time it was due
and sent, the arrival time and token ids of each token-bearing SSE event,
the finish reason and any failure. Times are ``time.monotonic()``.

The SSE parsing follows ``chip_smoke.py``'s client: frames are
``data: <json>`` separated by blank lines, ending with ``data: [DONE]``.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import threading
import time
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass
class Record:
    index: int
    due: float                      # monotonic time the request was due
    sent: Optional[float] = None
    events: List[float] = dataclasses.field(default_factory=list)
    counts: List[int] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    error: Optional[str] = None
    cancelled: bool = False         # cut off by the benchmark at the end

    @property
    def first(self) -> Optional[float]:
        return self.events[0] if self.events else None

    @property
    def done(self) -> bool:
        return self.finish is not None or self.error is not None


class LoadClient:
    """Drives one schedule against ``host:port`` on a background thread.

    ``start(requests, t0)`` schedules ``requests`` (``traffic.Request``)
    at ``t0 + due_s``; ``stop()`` cancels whatever is still in flight and
    joins the thread. ``sampler`` (optional) is called every
    ``sample_every_s`` on the loop's thread with the current time.
    """

    def __init__(self, host: str, port: int,
                 sampler: Optional[Callable[[float], None]] = None,
                 sample_every_s: float = 0.01):
        self.host, self.port = host, port
        self.sampler = sampler
        self.sample_every_s = sample_every_s
        self.records: Dict[int, Record] = {}
        self.lateness: List[float] = []      # send time - due time
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._tasks: List[asyncio.Task] = []
        self._stop_sending = threading.Event()

    # -- control (any thread) ------------------------------------------------
    def start(self, requests, t0: float):
        ready = threading.Event()
        self._thread = threading.Thread(
            target=self._main, args=(requests, t0, ready), daemon=True,
            name="bench-load")
        self._thread.start()
        ready.wait(timeout=30)

    def stop_sending(self):
        self._stop_sending.set()

    def stop(self, timeout_s: float = 30.0):
        """Cancel every request still in flight and end the thread."""
        self._stop_sending.set()
        loop = self._loop
        if loop is not None and not loop.is_closed():
            try:
                loop.call_soon_threadsafe(self._cancel_all)
            except RuntimeError:
                pass
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            if self._thread.is_alive():
                raise RuntimeError("load client did not stop")

    # -- loop thread ---------------------------------------------------------
    def _main(self, requests, t0, ready):
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        ready.set()
        try:
            self._loop.run_until_complete(self._run(requests, t0))
        finally:
            self._loop.close()

    def _cancel_all(self):
        for t in self._tasks:
            t.cancel()

    async def _run(self, requests, t0):
        sampler = (asyncio.ensure_future(self._sample())
                   if self.sampler else None)
        for req in sorted(requests, key=lambda r: r.due_s):
            if self._stop_sending.is_set():
                break
            due = t0 + req.due_s
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            if self._stop_sending.is_set():
                break
            rec = Record(req.index, due)
            self.records[req.index] = rec
            self._tasks.append(asyncio.ensure_future(self._one(req, rec)))
        while not self._stop_sending.is_set():
            await asyncio.sleep(0.05)
        live = [t for t in self._tasks if not t.done()]
        for t in live:
            t.cancel()
        if live:
            await asyncio.gather(*live, return_exceptions=True)
        if sampler is not None:
            sampler.cancel()
            await asyncio.gather(sampler, return_exceptions=True)

    async def _sample(self):
        while True:
            self.sampler(time.monotonic())
            await asyncio.sleep(self.sample_every_s)

    async def _one(self, req, rec: Record):
        writer = None
        try:
            reader, writer = await asyncio.open_connection(self.host,
                                                           self.port)
            body = json.dumps({"prompt": req.prompt.tolist(),
                               "max_tokens": req.max_tokens,
                               "stream": True}).encode()
            writer.write(b"POST /v1/completions HTTP/1.1\r\n"
                         b"Host: bench\r\nContent-Type: application/json\r\n"
                         + f"Content-Length: {len(body)}\r\n\r\n".encode()
                         + body)
            rec.sent = time.monotonic()
            self.lateness.append(rec.sent - rec.due)
            await writer.drain()
            status = await reader.readline()
            parts = status.split()
            code = int(parts[1]) if len(parts) > 1 else 0
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            if code != 200:
                rec.error = f"HTTP {code}: {(await reader.read(300))!r}"
                return
            await self._read_stream(reader, rec)
        except asyncio.CancelledError:
            rec.cancelled = True
            raise
        except (OSError, ValueError, asyncio.IncompleteReadError) as e:
            rec.error = f"{type(e).__name__}: {e}"
        finally:
            if writer is not None:
                writer.close()

    @staticmethod
    async def _read_stream(reader, rec: Record):
        while True:
            line = await reader.readline()
            if not line:
                rec.error = rec.error or "stream ended without [DONE]"
                return
            line = line.strip()
            if not line:
                continue
            if not line.startswith(b"data: "):
                rec.error = f"bad SSE line {line[:80]!r}"
                return
            data = line[6:]
            if data == b"[DONE]":
                if rec.finish is None:
                    rec.error = "stream ended without a finish reason"
                return
            frame = json.loads(data)
            if "error" in frame:
                rec.error = str(frame["error"])[:300]
                return
            choice = frame["choices"][0]
            if choice["token_ids"]:
                rec.events.append(time.monotonic())
                rec.counts.append(len(choice["token_ids"]))
                rec.tokens.extend(choice["token_ids"])
            if choice["finish_reason"]:
                rec.finish = choice["finish_reason"]
