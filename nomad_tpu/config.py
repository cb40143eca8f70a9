"""Agent configuration (reference command/agent/config.go +
config_parse.go): HCL or JSON config files merged with defaults and
flags.

    # agent.hcl
    data_dir = "/var/lib/nomad-tpu"
    server {
      enabled        = true
      num_schedulers = 4
      batch_pipeline = true
      heartbeat_ttl  = "30s"
    }
    client {
      enabled = true
      drivers = ["exec", "raw_exec", "mock_driver"]
    }
    http { port = 4646 }
    acl { enabled = false }
    telemetry { prometheus = true }
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class ServerConfig:
    enabled: bool = True
    num_schedulers: int = 2
    batch_pipeline: bool = True
    heartbeat_ttl_s: float = 30.0
    seed: Optional[int] = None


@dataclass
class ClientConfig:
    enabled: bool = False
    drivers: List[str] = field(
        default_factory=lambda: ["exec", "raw_exec", "mock_driver"]
    )
    include_tpu_fingerprint: bool = True
    heartbeat_interval_s: float = 10.0


@dataclass
class DeviceConfig:
    """Accelerator supervisor knobs (nomad_tpu/device).  ``None``
    defers to the NOMAD_TPU_* env knob (and its default), so a config
    file only pins what it names:

        device {
          probe_interval  = "30s"
          probe_timeout   = "10s"
          watchdog_min    = "5s"
          watchdog_max    = "2m"
        }
    """

    probe_interval_s: Optional[float] = None
    probe_timeout_s: Optional[float] = None
    watchdog_min_s: Optional[float] = None
    watchdog_max_s: Optional[float] = None
    lost_probes: Optional[int] = None
    recover_canaries: Optional[int] = None
    init_grace_s: Optional[float] = None


@dataclass
class HTTPConfig:
    host: str = "127.0.0.1"
    port: int = 4646


@dataclass
class ACLConfig:
    enabled: bool = False


@dataclass
class ConsulConfig:
    """(reference nomad/structs/config/consul.go)"""

    address: str = ""  # empty = in-framework catalog only
    token: str = ""


@dataclass
class VaultConfig:
    """(reference nomad/structs/config/vault.go)"""

    address: str = ""  # empty = local secrets providers only
    token: str = ""


@dataclass
class AgentConfig:
    data_dir: str = ""
    name: str = ""
    datacenter: str = "dc1"
    region: str = "global"
    server: ServerConfig = field(default_factory=ServerConfig)
    client: ClientConfig = field(default_factory=ClientConfig)
    device: DeviceConfig = field(default_factory=DeviceConfig)
    http: HTTPConfig = field(default_factory=HTTPConfig)
    acl: ACLConfig = field(default_factory=ACLConfig)
    consul: ConsulConfig = field(default_factory=ConsulConfig)
    vault: VaultConfig = field(default_factory=VaultConfig)
    bridge_port: Optional[int] = None


def _duration_s(value, default: float) -> float:
    """Canonical Go-style duration parser ("1h30m", "10s", "100ms",
    bare numbers).  The single shared implementation — jobspec and the
    mock driver import this one; keeping copies in sync is how the
    '100ms parses as 100 minutes' alternation bug happened."""
    if value is None:
        return default
    if isinstance(value, (int, float)):
        return float(value)
    s = str(value).strip()
    try:
        return float(s)
    except ValueError:
        pass
    total = 0.0
    # 'ms' must precede 'm' in the alternation or "100ms" reads as
    # 100 minutes
    for num, unit in re.findall(r"(-?[\d.]+)(ms|h|m|s)", s):
        total += float(num) * {"h": 3600, "m": 60, "s": 1, "ms": 0.001}[
            unit
        ]
    return total if total else default


def _first(value, default=None):
    if isinstance(value, list):
        return value[0] if value else default
    return value if value is not None else default


def config_from_dict(raw: Dict) -> AgentConfig:
    cfg = AgentConfig()
    cfg.data_dir = raw.get("data_dir", "")
    cfg.name = raw.get("name", "")
    cfg.datacenter = raw.get("datacenter", "dc1")
    cfg.region = raw.get("region", "global")

    server = _first(raw.get("server"), {}) or {}
    cfg.server = ServerConfig(
        enabled=bool(server.get("enabled", True)),
        num_schedulers=int(server.get("num_schedulers", 2)),
        batch_pipeline=bool(server.get("batch_pipeline", True)),
        heartbeat_ttl_s=_duration_s(server.get("heartbeat_ttl"), 30.0),
        seed=server.get("seed"),
    )
    client = _first(raw.get("client"), {}) or {}
    cfg.client = ClientConfig(
        enabled=bool(client.get("enabled", False)),
        drivers=client.get("drivers")
        or ["exec", "raw_exec", "mock_driver"],
        include_tpu_fingerprint=bool(
            client.get("include_tpu_fingerprint", True)
        ),
        heartbeat_interval_s=_duration_s(
            client.get("heartbeat_interval"), 10.0
        ),
    )
    device = _first(raw.get("device"), {}) or {}

    def _dur_or_none(key):
        value = device.get(key)
        return None if value is None else _duration_s(value, 0.0)

    cfg.device = DeviceConfig(
        probe_interval_s=_dur_or_none("probe_interval"),
        probe_timeout_s=_dur_or_none("probe_timeout"),
        watchdog_min_s=_dur_or_none("watchdog_min"),
        watchdog_max_s=_dur_or_none("watchdog_max"),
        lost_probes=(
            None
            if device.get("lost_probes") is None
            else int(device["lost_probes"])
        ),
        recover_canaries=(
            None
            if device.get("recover_canaries") is None
            else int(device["recover_canaries"])
        ),
        init_grace_s=_dur_or_none("init_grace"),
    )
    http = _first(raw.get("http"), {}) or {}
    cfg.http = HTTPConfig(
        host=http.get("host", "127.0.0.1"),
        port=int(http.get("port", 4646)),
    )
    acl = _first(raw.get("acl"), {}) or {}
    cfg.acl = ACLConfig(enabled=bool(acl.get("enabled", False)))
    consul = _first(raw.get("consul"), {}) or {}
    cfg.consul = ConsulConfig(
        address=consul.get("address", ""),
        token=consul.get("token", ""),
    )
    vault = _first(raw.get("vault"), {}) or {}
    cfg.vault = VaultConfig(
        address=vault.get("address", ""),
        token=vault.get("token", ""),
    )
    if raw.get("bridge_port") is not None:
        cfg.bridge_port = int(raw["bridge_port"])
    return cfg


def load_config(path: str) -> AgentConfig:
    with open(path) as f:
        text = f.read()
    if path.endswith(".json"):
        return config_from_dict(json.loads(text))
    # reuse the jobspec HCL machinery for the config dialect
    from .jobspec import _Parser, _tokenize

    tree = _Parser(_tokenize(text)).parse_body(stop=None)
    return config_from_dict(tree)
