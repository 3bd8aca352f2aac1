"""Layered runtime configuration from environment variables.

Analogue of the reference's Figment-based config
(reference: lib/runtime/src/config.rs:26-177 — DYN_RUNTIME_*/DYN_WORKER_*
env + TOML). Here: dataclass defaults ← optional JSON/TOML file
(DYN_CONFIG_PATH) ← DYN_* env vars, later layers win.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass
from typing import Any, Optional


@dataclass
class RuntimeConfig:
    # coordinator store location
    store_host: str = "127.0.0.1"
    store_port: int = 4222
    # run without a coordinator: single-process in-memory store
    static: bool = False
    # worker data-plane bind
    worker_host: str = "0.0.0.0"
    # host other processes should use to reach this worker
    advertise_host: str = "127.0.0.1"
    worker_port: int = 0  # 0 = ephemeral
    # The TTL has to outlast the renewal interval plus the longest freeze
    # a LIVE host shows: a four-chip v5e host froze for 7.8 s while four
    # 8B workers cold-started (an idle process's wake-up delay; my chip
    # run, PR 21), and 3 + 7.8 > the 10 s this was. A store on the same
    # host forgives that (its sweeper extends leases by its own deaf
    # time); one on another host cannot know. Process death is noticed
    # at once either way (a dropped connection revokes its leases), so
    # the TTL only bounds how long a vanished HOST stays listed.
    lease_ttl_s: float = 20.0
    lease_keepalive_s: float = 3.0
    request_timeout_s: float = 600.0
    log_level: str = "INFO"
    log_jsonl: bool = False

    ENV_PREFIX = "DYN_"

    @classmethod
    def from_settings(cls, **overrides: Any) -> "RuntimeConfig":
        values: dict[str, Any] = {}
        path = os.environ.get("DYN_CONFIG_PATH")
        if path and os.path.exists(path):
            with open(path) as f:
                if path.endswith(".toml"):
                    import tomllib

                    values.update(tomllib.loads(f.read()))
                else:
                    values.update(json.load(f))
        for f_ in dataclasses.fields(cls):
            env_key = cls.ENV_PREFIX + f_.name.upper()
            raw: Optional[str] = os.environ.get(env_key)
            if raw is None:
                continue
            if f_.type in ("int", int):
                values[f_.name] = int(raw)
            elif f_.type in ("float", float):
                values[f_.name] = float(raw)
            elif f_.type in ("bool", bool):
                values[f_.name] = raw.lower() in ("1", "true", "yes", "on")
            else:
                values[f_.name] = raw
        known = {f_.name for f_ in dataclasses.fields(cls)}
        values = {k: v for k, v in values.items() if k in known}
        values.update(overrides)
        return cls(**values)
