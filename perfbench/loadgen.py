"""The load generator: one process, one thread, no JAX.

Started by the serving driver as a child, so that the clients do not
share an interpreter lock with the engine they load. Reads one JSON
line from stdin, ``{"port", "plan"}`` (``perfbench/traffic.py`` makes
the plan), sends every request to ``POST /generate`` with ``"stream":
true`` over loopback, and stamps every streamed token with
``time.monotonic()`` (one clock for every process on a Linux host).
On a second stdin line (``stop``) it drops what is still open and
writes one JSON line of records to stdout:

    {"t0": <start of load>, "records": [{"id", "due", "sent", "times",
      "tokens", "done", "final", "error"}, ...]}

A closed plan also prints ``FILLED <t>`` once every lane has had a
first token.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def one_request(port: int, req: dict, rec: dict,
                      on_first=None) -> None:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        body = json.dumps({"prompt_ids": req["prompt_ids"],
                           "max_new_tokens": req["max_new_tokens"],
                           "stream": True}).encode()
        writer.write(
            b"POST /generate HTTP/1.1\r\nHost: perfbench\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: %d\r\n\r\n" % len(body) + body)
        rec["sent"] = time.monotonic()
        status = await reader.readline()
        if b" 200 " not in status:
            rest = await reader.read(400)
            rec["error"] = (status + rest).decode("latin-1")[:300]
            return
        while True:
            line = await reader.readline()
            if not line:
                rec.setdefault("error", "stream closed before done")
                return
            line = line.strip()
            if not line.startswith(b"{"):
                continue  # headers, chunk sizes, chunk ends
            msg = json.loads(line)
            now = time.monotonic()
            if "token" in msg:
                rec["tokens"].append(msg["token"])
                rec["times"].append(now)
                if on_first is not None and len(rec["times"]) == 1:
                    on_first()
            elif msg.get("done"):
                rec["done"] = now
                rec["final"] = msg["tokens"]
                return
            else:
                rec["error"] = str(msg.get("error", msg))[:300]
                return
    except (OSError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        writer.close()


def new_record(req: dict, due: float | None = None) -> dict:
    return {"id": req["id"], "asked": req["max_new_tokens"], "due": due,
            "sent": None, "times": [], "tokens": [], "done": None,
            "final": None, "error": None}


async def run_closed(port: int, plan: dict, records: list) -> None:
    first = [asyncio.Event() for _ in plan["lanes"]]

    async def lane(i: int, reqs: list) -> None:
        for req in reqs:
            rec = new_record(req)
            records.append(rec)
            await one_request(port, req, rec, first[i].set)
            if rec["error"]:
                return  # a failed lane stays failed; the parent counts it

    async def announce() -> None:
        for ev in first:
            await ev.wait()
        print(f"FILLED {time.monotonic()!r}", flush=True)

    await asyncio.gather(announce(), *(
        lane(i, reqs) for i, reqs in enumerate(plan["lanes"])))


async def run_open(port: int, plan: dict, records: list,
                   t0: float) -> None:
    tasks = []
    for req in plan["requests"]:
        due = t0 + req["due_s"]
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = new_record(req, due)
        records.append(rec)
        tasks.append(asyncio.ensure_future(one_request(port, req, rec)))
    await asyncio.gather(*tasks)


async def main() -> int:
    loop = asyncio.get_running_loop()
    job = json.loads(await loop.run_in_executor(None, sys.stdin.readline))
    port, plan = job["port"], job["plan"]
    records: list = []
    t0 = time.monotonic()
    print(f"STARTED {t0!r}", flush=True)
    if plan["loop"] == "closed":
        work = asyncio.ensure_future(run_closed(port, plan, records))
    else:
        work = asyncio.ensure_future(run_open(port, plan, records, t0))
    stop = loop.run_in_executor(None, sys.stdin.readline)
    await asyncio.wait([work, stop], return_when=asyncio.FIRST_COMPLETED)
    if not stop.done():
        await stop  # the plan ran out; wait to be told to report
    work.cancel()
    for task in asyncio.all_tasks():
        if task is not asyncio.current_task():
            task.cancel()
    await asyncio.sleep(0)
    print(json.dumps({"t0": t0, "records": records}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
