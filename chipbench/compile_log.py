"""Backend compilations, counted from ``jax.monitoring`` events.

Copied from ``chip_smoke.CompileLog``: every jitted program that reaches
the backend compiler fires one compile-duration event, whether or not
the persistent cache then supplies the executable.
"""
from __future__ import annotations


class CompileLog:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.programs = []          # (name, seconds), in compile order

    def __call__(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.programs.append((fun_name, duration))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)

    def mark(self) -> int:
        return len(self.programs)

    def since(self, mark: int) -> list:
        return self.programs[mark:]
