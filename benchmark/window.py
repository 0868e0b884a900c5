"""What a traffic generator hands back: the requests of one measured window."""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Window:
    t0: float = 0.0
    #: ``time.time()`` at the window's start (the program's traces use it)
    wall0: float = 0.0
    deadline: float = 0.0
    #: (client, k, start, end, ok) on ``time.perf_counter``, by completion
    requests: list = field(default_factory=list)
    #: ``repr`` of what each failed request raised
    errors: list = field(default_factory=list)

    @property
    def completed(self) -> list:
        return [r for r in self.requests if r[4]]

    @property
    def end(self) -> float:
        return max((r[3] for r in self.requests), default=self.t0)
