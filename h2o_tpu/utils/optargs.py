"""OptArgs — the unified flag system (`water/H2O.OptArgs` analog).

The reference parses one flat class of CLI flags reflectively
(`water/H2O.java:343-474,581-588`) and prints them with `printHelp`
(`water/H2O.java:69`). This is the same design over a dataclass: every
launcher/runtime knob lives HERE with its type, default, env-var spelling
and help line; `parse()` resolves CLI > environment > default and then
EXPORTS the resolved values back into the process environment — the ~20
existing `os.environ.get("H2O_TPU_*")` consumers scattered through the
runtime keep working unchanged, with this class as the single documented
surface over them (the `H2O.ARGS` global role).

Per-model hyperparameters are NOT flags — they are Parameters dataclasses,
schema-exposed (same rule as the reference).

Usage:
    python -m h2o_tpu.deploy_entry --help
    python -m h2o_tpu.deploy_entry --port 54321 --name my_cloud
    ARGS = optargs.parse(sys.argv[1:])
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Optional


def _flag(default, env: str | None, help_: str):
    return field(default=default,
                 metadata={"env": env, "help": help_})


@dataclass
class OptArgs:
    """Every launcher/runtime flag. CLI spelling is ``--<field-name>``
    (underscores or dashes both accepted, like the reference's `-name`)."""

    # -- identity / networking (`OptArgs.name/port/ip/flatfile`) -----------
    name: str = _flag("h2o_tpu", None,
                      "cloud name reported by /3/Cloud")
    port: int = _flag(54321, "H2O_TPU_REST_PORT",
                      "REST API port")
    ip: str = _flag("0.0.0.0", None,
                    "bind address for the REST server")
    baseport: int = _flag(0, None,
                          "first port to probe when `port` is taken "
                          "(0 = fail instead of scanning)")
    flatfile: str = _flag("", None,
                          "path to a flatfile of cluster nodes "
                          "(multi-host boot; one IPv4/IPv6[:port] per line)")
    driver: str = _flag("", "H2O_TPU_DRIVER",
                        "python module to run as the multi-host driver "
                        "instead of serving REST")
    assisted_clustering: bool = _flag(False, "H2O_TPU_ASSISTED_CLUSTERING",
                                      "start the clustering sidecar API and "
                                      "wait for a flatfile POST before "
                                      "touching any JAX backend")
    assisted_clustering_api_port: int = _flag(
        8080, "H2O_TPU_ASSISTED_CLUSTERING_API_PORT",
        "port for the assisted-clustering sidecar API")

    # -- storage / memory (`OptArgs.ice_root`, Cleaner knobs) --------------
    ice_root: str = _flag("", "H2O_TPU_ICE_DIR",
                          "spill directory for the HBM Cleaner "
                          "(default: a temp dir)")
    hbm_limit_bytes: int = _flag(0, "H2O_TPU_HBM_LIMIT_BYTES",
                                 "soft HBM budget before the Cleaner spills "
                                 "LRU vecs (0 = backend default)")
    max_frame_bytes: int = _flag(0, "H2O_TPU_MAX_FRAME_BYTES",
                                 "refuse parses whose frame would exceed "
                                 "this (FrameSizeMonitor; 0 = no cap)")
    nps_dir: str = _flag("", "H2O_TPU_NPS_DIR",
                         "NodePersistentStorage root directory")

    # -- security ----------------------------------------------------------
    hash_login: str = _flag("", None,
                            "realm file of user:sha256 lines for REST "
                            "basic auth")
    ldap_login: str = _flag("", None,
                            "LDAP URL for REST basic auth (ldap://host:389/"
                            "dn-pattern)")
    kerberos_login: bool = _flag(False, None,
                                 "accept SPNEGO/Kerberos on the REST plane")
    pam_login: bool = _flag(False, None,
                            "authenticate REST basic auth against PAM")
    ssl_certfile: str = _flag("", None, "TLS certificate for the REST port")
    ssl_keyfile: str = _flag("", None, "TLS private key for the REST port")
    allow_wire_udf: bool = _flag(False, "H2O_TPU_ALLOW_WIRE_UDF",
                                 "allow python: UDF references uploaded "
                                 "over the wire to execute")

    # -- engine knobs (sys.ai.h2o.* expert-prop analog) --------------------
    exact_bin_rows: int = _flag(16384, "H2O_TPU_EXACT_BIN_ROWS",
                                "rows at or below which tree binning uses "
                                "exact small-data cut points")
    clear_caches_every: int = _flag(0, "H2O_TPU_CLEAR_CACHES_EVERY",
                                    "drop live XLA executables every N "
                                    "finished jobs that built a program "
                                    "(long-running-server hygiene; "
                                    "0 = never)")
    pdp_batch_rows: int = _flag(2_000_000, "H2O_TPU_PDP_BATCH_ROWS",
                                "row budget per batched partial-dependence "
                                "predict")

    # -- external systems --------------------------------------------------
    webhdfs_url: str = _flag("", "H2O_TPU_WEBHDFS_URL",
                             "WebHDFS endpoint for hdfs:// persist")
    webhdfs_port: int = _flag(9870, "H2O_TPU_WEBHDFS_PORT",
                              "WebHDFS port when hdfs:// URIs carry none")
    hdfs_user: str = _flag("", "H2O_TPU_HDFS_USER",
                           "user.name forwarded to WebHDFS")
    hive_jdbc: str = _flag("", "H2O_TPU_HIVE_JDBC",
                           "Hive JDBC endpoint for ImportHiveTable")

    # -- logging -----------------------------------------------------------
    log_level: str = _flag("INFO", None,
                           "TRACE|DEBUG|INFO|WARN|ERRR|FATA")

    def export_env(self) -> None:
        """Write every env-backed resolved value back into os.environ so the
        scattered runtime consumers observe the flag values (the H2O.ARGS
        global, realized through the environment)."""
        for f in dataclasses.fields(self):
            env = f.metadata.get("env")
            if not env:
                continue
            v = getattr(self, f.name)
            if isinstance(v, bool):
                if v:
                    os.environ[env] = "1"
                else:
                    os.environ.pop(env, None)
            elif v not in ("", None) and v != f.default:
                os.environ[env] = str(v)


def _coerce(f: dataclasses.Field, raw: str):
    if f.type in ("int", int):
        return int(raw)
    if f.type in ("bool", bool):
        return str(raw).lower() in ("1", "true", "yes", "on")
    return raw


def parse(argv: list[str] | None = None) -> OptArgs:
    """CLI > environment > default, reflectively over the dataclass
    (`water/H2O.java:581-588` parseArguments)."""
    args = OptArgs()
    # environment layer
    for f in dataclasses.fields(OptArgs):
        env = f.metadata.get("env")
        if env and os.environ.get(env) not in (None, ""):
            try:
                setattr(args, f.name, _coerce(f, os.environ[env]))
            except ValueError:
                raise SystemExit(
                    f"bad value for {env}: {os.environ[env]!r}")
    # CLI layer
    fields = {f.name: f for f in dataclasses.fields(OptArgs)}
    argv = list(argv or [])
    i = 0
    while i < len(argv):
        tok = argv[i]
        if tok in ("--help", "-help", "-h"):
            print(help_text())
            raise SystemExit(0)
        name = tok.lstrip("-").replace("-", "_")
        f = fields.get(name)
        if f is None:
            raise SystemExit(f"unknown flag {tok!r} (see --help)")
        if f.type in ("bool", bool):
            # `--flag` or `--flag true/false`
            if i + 1 < len(argv) and argv[i + 1].lower() in (
                    "true", "false", "1", "0"):
                setattr(args, name, _coerce(f, argv[i + 1]))
                i += 2
            else:
                setattr(args, name, True)
                i += 1
            continue
        if i + 1 >= len(argv):
            raise SystemExit(f"flag {tok!r} needs a value")
        try:
            setattr(args, name, _coerce(f, argv[i + 1]))
        except ValueError:
            raise SystemExit(f"bad value for {tok}: {argv[i + 1]!r}")
        i += 2
    args.export_env()
    return args


def help_text() -> str:
    """`printHelp` analog — generated from the dataclass metadata."""
    lines = ["usage: python -m h2o_tpu.deploy_entry [flags]", "",
             "Flags (CLI > environment > default):", ""]
    for f in dataclasses.fields(OptArgs):
        env = f.metadata.get("env")
        default = f.default
        spec = f"  --{f.name.replace('_', '-'):<32}"
        lines.append(spec + f.metadata["help"])
        detail = f"        default: {default!r}"
        if env:
            detail += f"   env: {env}"
        lines.append(detail)
    return "\n".join(lines)


#: the resolved flags for this process (the `H2O.ARGS` global); deploy_entry
#: re-parses with the real argv
ARGS = OptArgs()
