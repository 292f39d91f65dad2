"""A served class whose timed path is broken underneath the harness: every
frame's first token is altered where it is produced."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.server import BenchServer  # noqa: E402


class BrokenServer(BenchServer):
    def generate(self, program):
        for frame in super().generate(program):
            if frame.get("tokens"):
                frame = dict(frame)
                toks = list(frame["tokens"])
                toks[0] = (toks[0] + 1) % self.cfg.vocab_size
                frame["tokens"] = toks
            yield frame
