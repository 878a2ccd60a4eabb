"""The load generator's client side: one thread, one asyncio loop, plain sockets.

Each request is a ``POST /v1/completions`` with ``stream=true`` over its own
connection; the server answers with chunked server-sent events, one per engine
emission. The client stamps every event with the host's monotonic clock as it
arrives, so time to first token and the time per output token are what a user of
the HTTP surface sees. Nothing here knows about the engine.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from perf.traffic.generate import Request


@dataclasses.dataclass
class Record:
    request: Request
    due: float = 0.0  # monotonic seconds
    sent: float = 0.0
    first: Optional[float] = None  # arrival of the first content event
    last: Optional[float] = None  # arrival of the last content event
    done: Optional[float] = None  # end of the response
    arrivals: List[Any] = dataclasses.field(default_factory=list)  # (time, tokens in the event)
    tokens: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    status: int = 0
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.status == 200 and len(self.tokens) == self.request.max_tokens

    def ttft_s(self) -> Optional[float]:
        return None if self.first is None else self.first - self.due

    def tpot_s(self) -> Optional[float]:
        if self.first is None or self.last is None or len(self.tokens) < 2:
            return None
        return (self.last - self.first) / (len(self.tokens) - 1)


async def _read_chunked(reader: asyncio.StreamReader, on_bytes: Callable[[bytes], None]) -> None:
    while True:
        size_line = await reader.readline()
        if not size_line:
            raise ConnectionError("connection closed inside a chunked body")
        size = int(size_line.split(b";", 1)[0].strip() or b"0", 16)
        if size == 0:
            await reader.readline()
            return
        data = await reader.readexactly(size)
        await reader.readexactly(2)
        on_bytes(data)


async def complete(port: int, record: Record, logprobs: bool, timeout_s: float) -> Record:
    """Send one request and read its event stream to the end; errors are
    recorded on the record, never raised."""
    request = record.request
    payload: Dict[str, Any] = {"prompt": request.prompt, "max_tokens": request.max_tokens, "stream": True, "temperature": 0}
    if logprobs:
        payload["logprobs"] = 1
    body = json.dumps(payload, separators=(",", ":")).encode()
    head = (
        f"POST /v1/completions HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\nContent-Type: application/json\r\n"
        f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
    ).encode()
    writer = None
    try:
        record.sent = time.monotonic()
        reader, writer = await asyncio.wait_for(asyncio.open_connection("127.0.0.1", port), timeout_s)
        writer.write(head + body)
        await writer.drain()
        status_line = await asyncio.wait_for(reader.readline(), timeout_s)
        record.status = int(status_line.split()[1])
        headers: Dict[str, str] = {}
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout_s)
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, value = line.decode("latin-1").partition(":")
            headers[key.strip().lower()] = value.strip().lower()
        if record.status != 200:
            length = int(headers.get("content-length", "0"))
            detail = (await reader.readexactly(length))[:300] if length else b""
            record.error = f"HTTP {record.status}: {detail!r}"
            return record
        buffer = bytearray()

        def on_bytes(data: bytes) -> None:
            now = time.monotonic()
            buffer.extend(data)
            while True:
                end = buffer.find(b"\n\n")
                if end < 0:
                    return
                event = bytes(buffer[:end])
                del buffer[: end + 2]
                if not event.startswith(b"data: ") or event == b"data: [DONE]":
                    continue
                choice = json.loads(event[6:])["choices"][0]
                block = choice.get("logprobs")
                if block:
                    ids = [int(t) for t in block["tokens"]]
                    record.logprobs.extend(float(x) for x in block["token_logprobs"])
                else:
                    ids = [int(t) for t in choice["text"].split()]
                if ids:
                    record.tokens.extend(ids)
                    record.arrivals.append((now, len(ids)))
                    if record.first is None:
                        record.first = now
                    record.last = now

        if "chunked" in headers.get("transfer-encoding", ""):
            await asyncio.wait_for(_read_chunked(reader, on_bytes), timeout_s)
        else:
            on_bytes(await asyncio.wait_for(reader.read(), timeout_s))
        record.done = time.monotonic()
    except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError, ValueError, KeyError, IndexError) as exc:
        record.error = f"{type(exc).__name__}: {exc}"
    finally:
        if writer is not None:
            writer.close()
    return record


async def closed_loop(port: int, pool: Sequence[Request], clients: int, stop_at: float, logprobs: bool,
                      timeout_s: float, records: List[Record]) -> None:
    """``clients`` callers, each sending its next request when the last one
    completed, until ``stop_at`` (monotonic); requests in flight then finish."""
    cursor = iter(pool)

    async def caller() -> None:
        while time.monotonic() < stop_at:
            request = next(cursor, None)
            if request is None:
                records.append(Record(Request(-1, [], 0), error="request pool exhausted before the window closed"))
                return
            record = Record(request)
            record.due = time.monotonic()
            records.append(record)
            await complete(port, record, logprobs, timeout_s)

    await asyncio.gather(*(caller() for _ in range(clients)))


async def open_loop(port: int, schedule: Sequence[Request], start: float, logprobs: bool, timeout_s: float,
                    records: List[Record]) -> None:
    """Every request is sent at ``start + due_s`` whatever the server does;
    latencies count from the due time, and ``sent - due`` is the generator's lag."""
    tasks = []
    for request in schedule:
        due = start + float(request.due_s or 0.0)
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        record = Record(request, due=due)
        records.append(record)
        tasks.append(asyncio.ensure_future(complete(port, record, logprobs, timeout_s)))
    if tasks:
        await asyncio.gather(*tasks)


def run_waves(port: int, waves: Sequence[Sequence[Request]], logprobs: bool, timeout_s: float) -> List[Record]:
    """Warm-up: each wave's requests together, a wave after the one before."""
    out: List[Record] = []

    async def go() -> None:
        for wave in waves:
            records = [Record(r, due=time.monotonic()) for r in wave]
            out.extend(records)
            await asyncio.gather(*(complete(port, r, logprobs, timeout_s) for r in records))

    asyncio.run(go())
    return out


def lag_summary(lags_s: List[float]) -> Dict[str, float]:
    """How late the generator ran: send time minus due time, over all requests sent."""
    if not lags_s:
        return {"n": 0}
    arr = np.asarray(lags_s)
    return {"n": int(arr.size), "p50_ms": float(np.percentile(arr, 50) * 1e3),
            "p95_ms": float(np.percentile(arr, 95) * 1e3), "max_ms": float(arr.max() * 1e3)}
