"""The client side: sends the generated requests through the gateway's
``EngineService.submit`` and stamps every token as its ``TokenStream``
hands it over.

Open loop: one sender thread starts each request at its absolute due time
(never sleep-by-gap, so lateness never adds up) and records how late it
ran.  Each request is a client thread of its own that submits and then
stamps its tokens on the host clock, so a submit that waits for the engine
delays only its own request, as it would for independent users.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    index: int
    due: float                    # absolute due time (perf_counter)
    greedy: bool
    prompt: np.ndarray
    max_new: int
    sent: float = 0.0
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


class Client:
    def __init__(self, service, sampling_greedy, sampling_sampled):
        self.service = service
        self.sp = {True: sampling_greedy, False: sampling_sampled}
        self.records: List[Record] = []
        self.late: List[float] = []
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._sender: Optional[threading.Thread] = None

    def _serve(self, rec: Record) -> None:
        rec.sent = time.perf_counter()
        try:
            stream = self.service.submit(
                rec.prompt.tolist(), sampling=self.sp[rec.greedy],
                max_new_tokens=rec.max_new)
        except Exception as exc:
            rec.error = repr(exc)
            return
        try:
            while True:
                try:
                    tok, done = stream.get(timeout=0.5)
                except queue.Empty:
                    if self._stop.is_set():
                        return
                    continue
                rec.stamps.append(time.perf_counter())
                rec.tokens.append(int(tok))
                if done:
                    rec.done = True
                    return
        except Exception as exc:          # engine closed or failed
            if not self._stop.is_set():
                rec.error = repr(exc)

    def _send(self, rec: Record) -> None:
        t = threading.Thread(target=self._serve, args=(rec,), daemon=True)
        t.start()
        self._threads.append(t)

    def start(self, reqs, t_start: float) -> None:
        """Send ``reqs`` at ``t_start + due_s`` each, from a sender thread."""
        self.records = [Record(index=r.index, due=t_start + r.due_s,
                               greedy=r.greedy, prompt=r.prompt,
                               max_new=r.max_new) for r in reqs]

        def sender():
            for rec in self.records:
                while True:
                    wait = rec.due - time.perf_counter()
                    if self._stop.is_set():
                        return
                    if wait <= 0:
                        break
                    time.sleep(min(wait, 0.2))
                self.late.append(time.perf_counter() - rec.due)
                self._send(rec)

        self._sender = threading.Thread(target=sender, daemon=True)
        self._sender.start()

    def reset(self) -> None:
        """Forget what was sent, to start a new schedule."""
        self.records, self.late, self._threads = [], [], []
        self._stop.clear()
        self._sender = None

    def stop(self) -> None:
        self._stop.set()
        if self._sender is not None:
            self._sender.join()

    def join_readers(self, timeout: float = 30.0) -> None:
        end = time.perf_counter() + timeout
        for t in self._threads:
            t.join(max(0.0, end - time.perf_counter()))
        alive = sum(t.is_alive() for t in self._threads)
        if alive:
            raise RuntimeError(f"{alive} client reader threads did not end")

    def lateness(self) -> dict:
        if not self.late:
            return {"p50_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
        x = np.asarray(self.late) * 1e3
        return {"p50_ms": float(np.percentile(x, 50)),
                "p99_ms": float(np.percentile(x, 99)),
                "max_ms": float(x.max())}
