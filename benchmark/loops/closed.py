"""Traffic generator ``"loop": "closed"``: ``clients`` callers, each waiting
for its reply before it sends its next request.

A traffic mix (``traffic/<mix>.json``) is data for the generator its ``loop``
key names: here ``clients``, how many callers there are. Each client calls
``request(client, k)``, which returns once its result is synced, and sends
the next one: callers that wait for a reply make a closed loop, so no rate is
searched for. Every request that starts before the deadline is finished and
recorded.
"""

from __future__ import annotations

import threading
import time

from window import Window


def run(request, traffic: dict, seconds: float, during=None) -> Window:
    """Run the mix for ``seconds``; ``during(window)``, if given, runs on the
    calling thread meanwhile (the traced slice)."""
    clients = traffic["clients"]
    win = Window()
    lock = threading.Lock()
    start = threading.Barrier(clients + 1)

    def client(c):
        start.wait()
        k = 0
        while time.perf_counter() < win.deadline:
            t_a = time.perf_counter()
            try:
                ok = bool(request(c, k))
            except Exception as exc:      # a failed request, counted as such
                ok = False
                win.errors.append(repr(exc))
            t_b = time.perf_counter()
            with lock:
                win.requests.append((c, k, t_a, t_b, ok))
            k += 1

    threads = [threading.Thread(target=client, args=(c,), name=f"client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    win.wall0 = time.time()
    win.t0 = time.perf_counter()
    win.deadline = win.t0 + seconds
    start.wait()
    try:
        if during is not None:
            during(win)
    finally:
        for t in threads:
            t.join()
    return win
