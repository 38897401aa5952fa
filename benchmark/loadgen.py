"""Load from one process and one thread: asyncio clients that speak HTTP to the
server on loopback as a user's would (`/v1/completions`, `"stream": true`) and
stamp every token with `time.monotonic()` as it is read. Closed loops send a
client's next request when its last is answered; open loops send on the
schedule the generator fixed, whatever the server does, and record how late
each request left."""
from __future__ import annotations

import asyncio
import contextlib
import json
import time


class Sample:
    """One request as its client saw it."""

    __slots__ = ("index", "prompt", "max_tokens", "shared_tokens", "due",
                 "sent", "status", "token_times", "tokens", "finish", "ended",
                 "error")

    def __init__(self, index, req, due):
        self.index = index
        self.prompt = req["prompt"]
        self.max_tokens = req["max_tokens"]
        self.shared_tokens = req["shared_tokens"]
        self.due = due            # monotonic seconds; None in a closed loop
        self.sent = None
        self.status = None        # HTTP status
        self.token_times = []
        self.tokens = []
        self.finish = None        # finish_reason of the last chunk
        self.ended = None         # monotonic seconds of [DONE] or failure
        self.error = None

    @property
    def refused(self):
        return self.status in (429, 503)

    @property
    def failed(self):
        """Refused, broken, or ended short of what was asked for. A request
        still in flight (no `ended`) has not failed."""
        if self.ended is None:
            return False
        return (self.status != 200 or self.error is not None
                or self.finish != "length"
                or len(self.tokens) != self.max_tokens)


class LoadGen:
    def __init__(self, port, requests, loop):
        self.port = port
        self.requests = requests
        self.loop = loop
        self.samples = []
        self.tokens_read = 0      # by every client, since the load started
        self.started = None
        self._tasks = set()
        self._stopping = False

    # -- driving ----------------------------------------------------------

    def start(self):
        self.started = time.monotonic()
        if self.loop["type"] == "closed":
            for _ in range(int(self.loop["clients"])):
                self._spawn(self._closed_client())
        else:
            self._spawn(self._open_dispatcher())

    def _spawn(self, coro):
        task = asyncio.ensure_future(coro)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
        return task

    def _next(self):
        req = next(self.requests)
        due = None if req["due_s"] is None else self.started + req["due_s"]
        s = Sample(len(self.samples), req, due)
        self.samples.append(s)
        return s

    async def _closed_client(self):
        while not self._stopping:
            await self._one(self._next())

    async def _open_dispatcher(self):
        while not self._stopping:
            s = self._next()
            delay = s.due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            if self._stopping:
                self.samples.pop()      # drawn and never sent
                return
            self._spawn(self._one(s))

    async def stop(self):
        """Stop sending, hang up on what is in flight (the server aborts a
        request whose client went away) and wait for every task to end."""
        self._stopping = True
        tasks = list(self._tasks)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    # -- one request ------------------------------------------------------

    async def _one(self, s):
        body = json.dumps({"prompt": s.prompt, "max_tokens": s.max_tokens,
                           "temperature": 0.0, "stream": True}).encode()
        writer = None
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", self.port)
            s.sent = time.monotonic()
            writer.write((
                "POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
            await writer.drain()
            head = await reader.readuntil(b"\r\n\r\n")
            s.status = int(head.split(b" ", 2)[1])
            if s.status != 200:
                s.error = (await reader.read()).decode(errors="replace")[:200]
                return
            while True:
                line = await reader.readline()
                if not line:
                    s.error = "stream closed before [DONE]"
                    return
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic()
                payload = line[6:].strip()
                if payload == b"[DONE]":
                    return
                choice = json.loads(payload)["choices"][0]
                for tok in choice["token_ids"]:
                    s.tokens.append(tok)
                    s.token_times.append(now)
                    self.tokens_read += 1
                s.finish = choice["finish_reason"] or s.finish
        except (ConnectionError, asyncio.IncompleteReadError, OSError) as e:
            s.error = f"{type(e).__name__}: {e}"
        finally:
            if not self._stopping or s.error is not None:
                s.ended = time.monotonic()
            if writer is not None:
                writer.close()
                with contextlib.suppress(ConnectionError, OSError,
                                         asyncio.CancelledError):
                    await writer.wait_closed()
