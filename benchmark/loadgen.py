"""The closed loop itself: one thread, one event loop, one connection a
slot.  A slot issues its viewer's next request when its last one has
returned; bodies are kept, nothing is decoded here."""

from __future__ import annotations

import asyncio
import time

import aiohttp


async def _slot(session, base: str, viewer_index: int, source, stop_at,
                think_s: float, out: list) -> None:
    while stop_at is None or time.perf_counter() < stop_at:
        req = source()
        if req is None:
            return
        t_issue = time.perf_counter()
        try:
            async with session.get(base + req["path"]) as resp:
                body = await resp.read()
                status = resp.status
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            body, status = repr(e).encode(), 0
        out.append({"viewer": viewer_index, "t_issue": t_issue,
                    "t_done": time.perf_counter(), "status": status,
                    "body": body, "req": req})
        if think_s:
            await asyncio.sleep(think_s)


async def _drive(port: int, sources: list, connections: int,
                 seconds, think_s: float, side_task) -> list:
    out: list = []
    base = f"http://127.0.0.1:{port}"
    timeout = aiohttp.ClientTimeout(total=300.0)
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=connector,
                                     auto_decompress=False) as session:
        t_start = time.perf_counter()
        stop_at = None if seconds is None else t_start + seconds
        tasks = [asyncio.ensure_future(
            _slot(session, base, v, source, stop_at, think_s, out))
            for v, source in enumerate(sources)
            for _ in range(connections)]
        side = None if side_task is None \
            else asyncio.ensure_future(side_task(session, base))
        await asyncio.gather(*tasks)
        t_stop = time.perf_counter() if stop_at is None else stop_at
        if side is not None:
            await side
    return out, t_start, t_stop


def require_all_ok(records: list, what: str) -> None:
    """Outside the window nothing may be refused: fail the run."""
    from benchmark.procs import check
    bad = [(r["status"], r["body"][:200]) for r in records
           if r["status"] != 200]
    check(not bad, f"{what}: {len(bad)} of {len(records)} requests "
          f"failed, first {bad[:1]}")


def drive(port: int, sources: list, connections: int, seconds=None,
          think_s: float = 0.0, side_task=None) -> tuple:
    """Run ``connections`` slots for each of ``sources`` (callables that
    give a viewer's next request, or None when it has no more) for
    ``seconds`` (None: until every source is dry).  Returns
    ``(records, t_start, t_stop)`` on ``time.perf_counter``'s clock;
    requests in flight at the stop are waited for."""
    return asyncio.run(_drive(port, sources, connections, seconds,
                              think_s, side_task))
