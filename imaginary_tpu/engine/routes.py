"""Which routes have a request on its way to the executor.

A request is on its way from the moment the web layer hands it to the
host pool (`ImageService._submit_pool`) until its `Executor.submit`, or
until any earlier exit: a decode error, an identity plan, a pool task
cancelled while still queued. Until then it may still join a pending
chunk of its route; after it, nothing of that request can.

The continuous collectors read this through `RouteLedger.none_coming`: a
pending chunk whose routes have nothing on its way can get no companion
before its formation cap, so it closes at once instead of waiting the
cap out. Items submitted without a token (embedders, direct
`Executor.submit` callers) carry no route and keep the cap.

The token rides into the pool thread on the contextvar below, in the
context `_submit_pool` copies. Every exit releases it; release is
idempotent, so a token that reaches more than one exit counts once.
"""

from __future__ import annotations

import contextvars
import threading
from typing import Optional

_TOKEN: contextvars.ContextVar = contextvars.ContextVar(
    "itpu_route_token", default=None)


class RouteToken:
    """One request's place in its route's count; `release` gives it up
    once, whoever calls it first."""

    __slots__ = ("route", "_ledger", "_held")

    def __init__(self, ledger: "RouteLedger", route: str):
        self.route = route
        self._ledger = ledger
        self._held = True

    def release(self) -> None:
        self._ledger._release(self)


class RouteLedger:
    """Per-route counts of requests on their way, under one lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: dict = {}  # route -> requests on their way (> 0)

    def take(self, route: str) -> RouteToken:
        with self._lock:
            self._counts[route] = self._counts.get(route, 0) + 1
        return RouteToken(self, route)

    def _release(self, token: RouteToken) -> None:
        with self._lock:
            if not token._held:
                return
            token._held = False
            n = self._counts[token.route] - 1
            if n:
                self._counts[token.route] = n
            else:
                del self._counts[token.route]

    def on_the_way(self, route: str) -> int:
        with self._lock:
            return self._counts.get(route, 0)

    def none_coming(self, items) -> bool:
        """True when every item has a route and no request of any of
        those routes is on its way: the chunk cannot grow."""
        routes = set()
        for it in items:
            if it.route is None:
                return False
            routes.add(it.route)
        with self._lock:
            return not any(r in self._counts for r in routes)


def bind(token: Optional[RouteToken]) -> None:
    """Make `token` the current request's (run inside the context copied
    for the pool thread)."""
    _TOKEN.set(token)


def current() -> Optional[RouteToken]:
    return _TOKEN.get()
