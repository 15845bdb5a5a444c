"""The benchmark's ``Request``: records every delivery it receives, and
finishes at its next delivery once the window has closed."""
from __future__ import annotations

from repro.serve import Request


class BenchRequest(Request):

    @classmethod
    def make(cls, a, run) -> "BenchRequest":
        r = cls(rid=a.rid, prompt=a.prompt, t_arrival=a.t_arrival,
                max_new_tokens=a.max_new_tokens, seed=a.seed)
        r.run = run
        return r

    def add_tokens(self, new_tokens, now: float) -> bool:
        n0 = len(self.tokens)
        done = super().add_tokens(new_tokens, now) \
            or self.run.phase == "closed"
        self.run.deliver(self, len(self.tokens) - n0, now, done)
        return done
