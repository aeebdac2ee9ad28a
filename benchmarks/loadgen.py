"""The load generator: a process of its own that never imports JAX.

``python loadgen.py <plan.json> <records.json>``.  The plan holds the
server's URL, the generated requests, the loop (``open`` with ``due``
times, or ``closed`` with a number of clients that each wait for their
reply) and the window.  It prints ``T0 <monotonic seconds>`` when the
window starts (CLOCK_MONOTONIC is shared by the processes of a machine,
so the parent can place its counters and its trace on the same clock),
and at the end one line of JSON with how late it ran.  The records file
gets, per request sent: index, due and sent time, the arrival time of
every streamed token, the finish reason, the HTTP status.

The window: no request is sent after ``window_s``.  Then it waits until
every request sent has its first token (at most ``FIRST_TOKEN_WAIT_S``)
and cuts what is still streaming: a cut request is neither failed nor
complete, and its tokens up to the cut count.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import aiohttp

NULL_FINISH = b'"finish_reason": null'
# After the window: how long to wait for the first token of what was sent.
FIRST_TOKEN_WAIT_S = 20.0


async def _one(session, url, req, t0, rec):
    """Send one request and stamp every streamed token."""
    body = {
        "prompt": req["prompt"],
        "max_tokens": req["max_tokens"],
        "temperature": req["temperature"],
        "top_p": req["top_p"],
        "stream": True,
    }
    rec["sent"] = time.monotonic() - t0
    try:
        async with session.post(url, json=body) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["finish"] = f"http_{resp.status}"
                return
            async for line in resp.content:
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic() - t0
                if NULL_FINISH in line:
                    rec["tokens"].append(now)
                elif line.startswith(b"data: [DONE]"):
                    break
                else:
                    choice = json.loads(line[6:])["choices"][0]
                    if choice.get("text"):
                        rec["tokens"].append(now)
                    rec["finish"] = choice.get("finish_reason")
    except asyncio.CancelledError:
        rec["finish"] = rec["finish"] or "cut"
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as exc:
        rec["finish"] = f"client_error:{type(exc).__name__}"
    finally:
        rec["end"] = time.monotonic() - t0


def _record(idx, req):
    return {
        "idx": idx,
        "due": req.get("due"),
        "sent": None,
        "tokens": [],
        "finish": None,
        "status": None,
        "end": None,
        "prompt_len": len(req["prompt"]),
        "max_tokens": req["max_tokens"],
    }


async def _run(plan: dict) -> tuple[list, dict]:
    url = plan["url"]
    requests = plan["requests"]
    window = float(plan["window_s"])
    records: list[dict] = []
    tasks: list[asyncio.Task] = []
    exhausted = False
    clients: list[asyncio.Task] = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
        t0 = time.monotonic() + 0.05
        print(f"T0 {t0!r}", flush=True)
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))

        def launch(idx):
            rec = _record(idx, requests[idx])
            records.append(rec)
            task = asyncio.create_task(_one(session, url, requests[idx], t0, rec))
            tasks.append(task)
            return task

        if plan["loop"] == "open":
            for idx, req in enumerate(requests):
                if req["due"] >= window:
                    break
                delay = t0 + req["due"] - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                launch(idx)
            else:
                exhausted = True
        else:
            next_idx = 0

            async def client():
                nonlocal next_idx, exhausted
                while time.monotonic() - t0 < window:
                    if next_idx >= len(requests):
                        exhausted = True
                        return
                    idx, next_idx = next_idx, next_idx + 1
                    await launch(idx)

            clients = [asyncio.create_task(client()) for _ in range(int(plan["clients"]))]
            await asyncio.sleep(max(0.0, t0 + window - time.monotonic()))
        # Wait for first tokens of what was sent, then cut the rest.
        deadline = time.monotonic() + FIRST_TOKEN_WAIT_S
        while time.monotonic() < deadline:
            if all(r["tokens"] or r["finish"] for r in records):
                break
            await asyncio.sleep(0.02)
        for t in tasks:
            t.cancel()
        # A closed-loop client ends with its request: it sends no other,
        # because the window is over.
        await asyncio.gather(*tasks, *clients, return_exceptions=True)
    lateness = sorted(
        (r["sent"] - r["due"]) * 1000.0
        for r in records
        if r["due"] is not None and r["sent"] is not None
    )
    summary = {
        "sent": len(records),
        "supply_exhausted": exhausted,
        "late_p95_ms": lateness[int(0.95 * (len(lateness) - 1))] if lateness else 0.0,
        "late_max_ms": lateness[-1] if lateness else 0.0,
    }
    return records, summary


def main(argv) -> int:
    plan_path, records_path = argv[1], argv[2]
    with open(plan_path) as f:
        plan = json.load(f)
    records, summary = asyncio.run(_run(plan))
    with open(records_path, "w") as f:
        json.dump({"records": records, "summary": summary}, f)
    print(json.dumps({"loadgen": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
