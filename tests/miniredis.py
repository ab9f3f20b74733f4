"""A tiny in-process RESP2 server implementing just the commands the
framework's Redis layer uses (SET/GET/DEL/EX, PUBLISH/SUBSCRIBE,
LPUSH/BRPOP, AUTH/SELECT).  Lets the RedisBus/RedisJobQueue path be tested
end-to-end over a real TCP socket without a Redis binary in the image."""

from __future__ import annotations

import asyncio
import time
from collections import defaultdict, deque


class MiniRedis:
    def __init__(self) -> None:
        self.kv: dict[str, tuple[str, float | None]] = {}
        self.lists: dict[str, deque[str]] = defaultdict(deque)
        self.subscribers: dict[str, list[asyncio.StreamWriter]] = defaultdict(list)
        self.server: asyncio.AbstractServer | None = None
        self.port: int | None = None
        self._handlers: set[asyncio.Task] = set()

    async def start(self) -> int:
        self.server = await asyncio.start_server(self._handle, "127.0.0.1", 0)
        self.port = self.server.sockets[0].getsockname()[1]
        return self.port

    async def stop(self) -> None:
        if self.server:
            self.server.close()
            # Python 3.12 made wait_closed() wait for every open connection:
            # a client that never hangs up (a test's leftover handle) would
            # hold it forever, so drop the connections like a dying server.
            # Looping catches a handler whose task had not started running
            # (and so had not registered) when the first sweep went by.
            while True:
                for task in list(self._handlers):
                    task.cancel()
                try:
                    await asyncio.wait_for(self.server.wait_closed(), timeout=0.1)
                    return
                except asyncio.TimeoutError:
                    continue

    async def _read_command(self, reader: asyncio.StreamReader) -> list[str] | None:
        line = await reader.readline()
        if not line:
            return None
        assert line[:1] == b"*", line
        n = int(line[1:-2])
        args = []
        for _ in range(n):
            hdr = await reader.readline()
            assert hdr[:1] == b"$"
            length = int(hdr[1:-2])
            data = await reader.readexactly(length + 2)
            args.append(data[:-2].decode("utf-8"))
        return args

    @staticmethod
    def _simple(s: str) -> bytes:
        return f"+{s}\r\n".encode()

    @staticmethod
    def _bulk(s: str | None) -> bytes:
        if s is None:
            return b"$-1\r\n"
        b = s.encode("utf-8")
        return b"$%d\r\n%s\r\n" % (len(b), b)

    @staticmethod
    def _int(i: int) -> bytes:
        return f":{i}\r\n".encode()

    @classmethod
    def _array(cls, items: list) -> bytes:
        out = [b"*%d\r\n" % len(items)]
        for it in items:
            if isinstance(it, int):
                out.append(cls._int(it))
            else:
                out.append(cls._bulk(it))
        return b"".join(out)

    def _get(self, key: str) -> str | None:
        entry = self.kv.get(key)
        if entry is None:
            return None
        val, expiry = entry
        if expiry is not None and time.monotonic() > expiry:
            del self.kv[key]
            return None
        return val

    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        self._handlers.add(asyncio.current_task())
        try:
            while True:
                args = await self._read_command(reader)
                if args is None:
                    return
                cmd = args[0].upper()
                if cmd in ("AUTH", "SELECT"):
                    writer.write(self._simple("OK"))
                elif cmd == "SET":
                    expiry = None
                    if len(args) >= 5 and args[3].upper() == "EX":
                        expiry = time.monotonic() + float(args[4])
                    self.kv[args[1]] = (args[2], expiry)
                    writer.write(self._simple("OK"))
                elif cmd == "GET":
                    writer.write(self._bulk(self._get(args[1])))
                elif cmd == "DEL":
                    existed = int(args[1] in self.kv)
                    self.kv.pop(args[1], None)
                    writer.write(self._int(existed))
                elif cmd == "PUBLISH":
                    channel, message = args[1], args[2]
                    receivers = self.subscribers.get(channel, [])
                    for w in list(receivers):
                        try:
                            w.write(self._array(["message", channel, message]))
                            await w.drain()
                        except (ConnectionError, OSError):
                            receivers.remove(w)
                    writer.write(self._int(len(receivers)))
                elif cmd == "SUBSCRIBE":
                    self.subscribers[args[1]].append(writer)
                    writer.write(self._array(["subscribe", args[1], 1]))
                elif cmd == "LPUSH":
                    self.lists[args[1]].appendleft(args[2])
                    writer.write(self._int(len(self.lists[args[1]])))
                elif cmd == "LLEN":
                    writer.write(self._int(len(self.lists.get(args[1], ()))))
                elif cmd == "BRPOP":
                    key, timeout = args[1], float(args[2])
                    deadline = time.monotonic() + (timeout or 1e9)
                    popped = None
                    while time.monotonic() < deadline:
                        if self.lists.get(key):
                            popped = self.lists[key].pop()
                            break
                        await asyncio.sleep(0.01)
                    writer.write(self._array([key, popped]) if popped is not None else b"*-1\r\n")
                else:
                    writer.write(f"-ERR unknown command '{cmd}'\r\n".encode())
                await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError, OSError):
            pass
        finally:
            self._handlers.discard(asyncio.current_task())
            for subs in self.subscribers.values():
                if writer in subs:
                    subs.remove(writer)
            writer.close()
