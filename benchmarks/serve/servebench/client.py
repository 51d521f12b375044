"""The load generator: a child process that never imports JAX.

It keeps the clients off the chip and off the engine thread's
interpreter lock.  Run by the benchmark as

    python3 client.py '<json args>'

with ``{"mix": {...}, "seed": n, "vocab": V, "slots": S, "n": N}``.  It
makes the request stream (:mod:`servebench.traffic`), prints ``ready``,
then reads one line ``{"url": ..., "start": ..., "end": ...}`` from its
standard input: ``time.perf_counter()`` instants at which the load starts
(the window's opening less the mix's ``warm_in_s``) and the window closes
(the clock is system-wide on Linux, so both processes share it).

* closed loop: the mix's clients start ``STAGGER_S`` apart from
  ``start``, and each takes the next request of the stream the moment
  its last one ended, until the window closes; requests still streaming then are cut (their connection is
  closed, and the server cancels them);
* open loop: each request is sent at ``start + due``; after the window
  closes nothing new is sent, every request due before it is waited on
  (at most ``GRACE_S``) until its first token, then the rest are cut.

It prints one JSON line: per request its send and due instants, how it
ended, its tokens and the instant each token arrived.  The HTTP client
is a copy of ``repro.launch.loadgen.stream_generate`` that also keeps
each token's arrival time.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time
import urllib.parse
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from servebench import traffic  # noqa: E402

# seconds past the window's close that an open loop waits for the first
# token of every request due in the window
GRACE_S = 60.0
# seconds between the starts of a closed loop's clients
STAGGER_S = 0.02


def _split(base_url: str) -> Tuple[str, int]:
    u = urllib.parse.urlparse(base_url)
    if u.scheme != "http" or u.hostname is None or u.port is None:
        raise ValueError(f"need an http://host:port base url, "
                         f"got {base_url!r}")
    return u.hostname, u.port


async def _read_head(reader) -> Tuple[int, Dict[str, str]]:
    head = await reader.readuntil(b"\r\n\r\n")
    lines = head.decode("latin1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        if ":" in ln:
            k, v = ln.split(":", 1)
            headers[k.strip().lower()] = v.strip()
    return status, headers


async def stream_generate(base_url: str, payload: dict, rec: dict,
                          first_token: Optional[asyncio.Event] = None,
                          timeout: float = 600.0) -> None:
    """POST /v1/generate and consume the SSE stream, filling ``rec``:
    ``status``, ``tokens``, ``times`` (arrival of each token event),
    ``terminal``, ``error``.  Cancelling the task closes the socket."""
    host, port = _split(base_url)
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = json.dumps(payload).encode()
        req = (f"POST /v1/generate HTTP/1.1\r\nHost: {host}\r\n"
               f"Content-Type: application/json\r\n"
               f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
        rec["sent"] = time.perf_counter()
        writer.write(req.encode() + body)
        await writer.drain()
        status, headers = await asyncio.wait_for(_read_head(reader), timeout)
        rec["status"] = status
        if status != 200:
            n = int(headers.get("content-length", 0) or 0)
            raw = await reader.readexactly(n) if n else b""
            doc = json.loads(raw) if raw else {}
            rec["error"] = doc.get("error", f"HTTP {status}")
            return
        if headers.get("transfer-encoding") != "chunked":
            rec["error"] = "response is not chunked"
            return
        final = None
        buf = b""
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout)
            size = int(line.strip() or b"0", 16)
            if size == 0:
                break
            buf += await reader.readexactly(size)
            await reader.readexactly(2)  # chunk CRLF
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                for ln in event.decode().splitlines():
                    if not ln.startswith("data:"):
                        continue
                    ev = json.loads(ln[5:].strip())
                    now = time.perf_counter()
                    if "token" in ev:
                        rec["tokens"].append(int(ev["token"]))
                        rec["times"].append(now)
                        if first_token is not None:
                            first_token.set()
                    elif ev.get("done"):
                        final = [int(t) for t in ev["tokens"]]
                        rec["terminal"] = str(ev.get("status", "completed"))
                        if "error" in ev:
                            rec["error"] = str(ev["error"])
                    elif "error" in ev:
                        rec["error"] = str(ev["error"])
        if final is None:
            rec["error"] = rec.get("error") or "stream ended without done"
        elif final != rec["tokens"]:
            rec["error"] = rec.get("error") or (
                f"final token list disagrees with the stream "
                f"({len(final)} vs {len(rec['tokens'])})")
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError, OSError):
            pass


def _record(r: traffic.Request, due: float) -> dict:
    return {"idx": r.idx, "due": due, "sent": None, "status": None,
            "tokens": [], "times": [], "terminal": None, "error": None,
            "cut": False}


def _payload(r: traffic.Request, mix: dict) -> dict:
    return {"prompt": r.prompt.tolist(), "max_new": r.max_new,
            "temperature": float(mix.get("temperature", 0.0)),
            "tag": r.idx}


async def _run_task(url, r, mix, rec, first):
    try:
        await stream_generate(url, _payload(r, mix), rec, first)
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except Exception as exc:  # noqa: BLE001 - recorded as the request's failure
        rec["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        first.set()


async def run_closed(url: str, mix: dict, reqs, n_clients: int, t0: float,
                     t_end: float) -> List[dict]:
    records: List[dict] = []
    stream = iter(reqs)
    ran_out = []

    async def client(k: int):
        # the clients start in turn, so that the engine admits the first
        # wave in the stream's order on every run
        await asyncio.sleep(max(0.0, t0 + k * STAGGER_S - time.perf_counter()))
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            r = next(stream, None)
            if r is None:
                ran_out.append(now)
                return
            rec = _record(r, now)
            records.append(rec)
            await _run_task(url, r, mix, rec, asyncio.Event())

    tasks = [asyncio.create_task(client(k)) for k in range(n_clients)]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t_end - time.perf_counter()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    if ran_out:
        raise RuntimeError(f"the request stream ran out {len(ran_out)} times "
                           f"inside the window: make the stream longer")
    return records


async def run_open(url: str, mix: dict, reqs, t0: float,
                   t_end: float) -> List[dict]:
    records: List[dict] = []
    tasks = []
    firsts = []
    for r in reqs:
        due = t0 + r.due
        if due >= t_end:
            break
        await asyncio.sleep(max(0.0, due - time.perf_counter()))
        rec = _record(r, due)
        records.append(rec)
        first = asyncio.Event()
        firsts.append(first)
        tasks.append(asyncio.create_task(_run_task(url, r, mix, rec, first)))
    else:
        raise RuntimeError("the request stream ran out inside the window: "
                           "make the stream longer")
    await asyncio.sleep(max(0.0, t_end - time.perf_counter()))
    # every request due in the window gets its first token (or ends)
    waits = [asyncio.create_task(f.wait()) for f in firsts]
    if waits:
        await asyncio.wait(waits, timeout=GRACE_S)
    for w in waits:
        w.cancel()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, *waits, return_exceptions=True)
    return records


def main() -> int:
    args = json.loads(sys.argv[1])
    mix = args["mix"]
    reqs = traffic.make_requests(mix, args["seed"], args["vocab"], args["n"])
    print("ready", flush=True)
    go = json.loads(sys.stdin.readline())
    t0, t_end = float(go["start"]), float(go["end"])
    if mix["loop"] == "closed":
        coro = run_closed(go["url"], mix, reqs,
                          traffic.clients(mix, args["slots"]), t0, t_end)
    else:
        coro = run_open(go["url"], mix, reqs, t0, t_end)
    records = asyncio.run(coro)
    sys.stdout.write(json.dumps({"records": records}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
