"""Closed-loop load generator (paper §III-B: each client sends requests in a
closed loop)."""

from __future__ import annotations

import time

import numpy as np

from repro.serving.request import Request


class ClosedLoopClient:
    """One closed-loop client. Prompts are ``prompt_len`` tokens long, or,
    with ``prompt_len_max``, drawn uniformly from
    ``[prompt_len, prompt_len_max]``. Every request made is kept in
    ``sent``; the engine fills each one's ``generated`` tokens."""

    def __init__(self, client_id: int, vocab: int, *, prompt_len: int = 32,
                 max_new_tokens: int = 8, priority: int = 0, seed: int = 0,
                 prompt_len_max: int = 0):
        self.client_id = client_id
        self.vocab = vocab
        self.prompt_len = prompt_len
        self.prompt_len_max = max(prompt_len_max, prompt_len)
        self.max_new_tokens = max_new_tokens
        self.priority = priority
        self.rng = np.random.default_rng(seed + client_id)
        self.inflight = None
        self.completed = []
        self.sent = []

    def make_request(self) -> Request:
        n = self.prompt_len
        if self.prompt_len_max > n:
            n = int(self.rng.integers(n, self.prompt_len_max + 1))
        toks = self.rng.integers(0, self.vocab, n, dtype=np.int32)
        req = Request(
            prompt_tokens=toks,
            max_new_tokens=self.max_new_tokens,
            priority=self.priority,
            client_id=self.client_id,
        )
        self.inflight = req.request_id
        self.sent.append(req)
        return req

    def complete(self, response):
        assert response.request_id == self.inflight
        self.inflight = None
        self.completed.append(response)


def run_closed_loop(engine, clients, requests_per_client: int):
    """Drive the engine with closed-loop clients until all finish."""
    remaining = {c.client_id: requests_per_client for c in clients}
    by_req = {}
    for c in clients:
        req = c.make_request()
        by_req[req.request_id] = c
        engine.submit(req, time.perf_counter())
        remaining[c.client_id] -= 1
    while True:
        done = engine.step()
        for rsp in done:
            c = by_req.pop(rsp.request_id)
            c.complete(rsp)
            if remaining[c.client_id] > 0:
                req = c.make_request()
                by_req[req.request_id] = c
                engine.submit(req, time.perf_counter())
                remaining[c.client_id] -= 1
        if not by_req and not engine.queue:
            break
    return clients
