"""Central ``DORA_*`` environment-variable registry and its lints.

Every env var the runtime reads is declared here once, with its type,
default, and whether it belongs in the README tables. Two lints keep the
registry honest:

* ``env-undeclared`` — an ``os.environ`` / ``os.getenv`` read of a
  ``DORA_*`` name (literal, or via a module-level string constant like
  ``NODE_CONFIG_ENV``) that is not in :data:`REGISTRY`.
* ``env-unregistered-literal`` — any *other* full ``DORA_*`` string
  literal in the package (helper-call sites like
  ``_slo_env("DORA_SLO_TTFT_P99_MS")``, spawn-side injections) that is
  neither registered nor a registered-name prefix (f-string heads such
  as ``"DORA_SLO_"``) nor on the non-env allowlist (C enum identifiers
  embedded in native source).
* ``env-readme-unknown`` / ``env-readme-missing`` — the README env
  tables and the registry must agree: every ``DORA_*`` token in the
  README is registered (or allowlisted / a registered prefix), and every
  registry entry marked ``readme=True`` appears in the README.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path

from dora_tpu.analysis import Finding

_TOKEN = re.compile(r"DORA_[A-Z0-9_]*")


@dataclass(frozen=True)
class EnvVar:
    name: str
    kind: str          # "bool" | "int" | "float" | "str" | "path"
    default: str       # rendered default, "" when unset means off/absent
    desc: str
    readme: bool = False  # must appear in a README env table


def _e(name, kind, default, desc, readme=False):
    return name, EnvVar(name, kind, default, desc, readme)


#: The single source of truth for runtime-read ``DORA_*`` env vars.
REGISTRY: dict[str, EnvVar] = dict((
    # --- telemetry / observability -------------------------------------
    _e("DORA_LOG", "str", "info", "log level for the structured logger", True),
    _e("DORA_TRACING", "bool", "0", "enable span tracing", True),
    _e("DORA_JAEGER_TRACING", "str", "", "Jaeger agent addr for span export", True),
    _e("DORA_FLIGHT_RECORDER", "bool", "0", "enable the in-memory flight recorder", True),
    _e("DORA_FLIGHT_RECORDER_SIZE", "int", "65536", "flight recorder ring capacity", True),
    _e("DORA_NO_STACK_DUMP", "bool", "0", "suppress SIGUSR1 stack dumps"),
    _e("DORA_METRICS_HISTORY_S", "float", "900", "metrics history window seconds", True),
    _e("DORA_METRICS_HISTORY_LEN", "int", "1800", "metrics history ring length", True),
    _e("DORA_ALERTS", "bool", "1", "evaluate alert rules over the metrics history", True),
    _e("DORA_ALERT_SINK", "str", "", "comma list of alert sinks: log, jsonl, webhook", True),
    _e("DORA_ALERT_SINK_FILE", "path", "", "JSONL alert sink output file", True),
    _e("DORA_ALERT_SINK_WEBHOOK", "str", "", "webhook alert sink POST URL", True),
    _e("DORA_ALERT_WEBHOOK_RETRIES", "int", "2", "extra webhook delivery attempts per alert", True),
    _e("DORA_FLEET_DIGEST_S", "float", "2.0", "engine-state digest publish cadence (0 disables)", True),
    _e("DORA_FLEET_TOP_PREFIXES", "int", "32", "cached prefixes per engine digest", True),
    _e("DORA_PROM_PORT", "int", "", "coordinator Prometheus exporter port", True),
    _e("DORA_DEVICE_MONITOR", "bool", "1", "sample HBM/MFU device gauges", True),
    _e("DORA_DEVICE_PEAK_FLOPS", "float", "", "override device peak FLOP/s for MFU", True),
    _e("DORA_PROFILE_DIR", "path", "", "on-demand XLA profile output dir", True),
    # --- lockcheck (analysis plane) ------------------------------------
    _e("DORA_LOCKCHECK", "bool", "0", "enable the lock-order race detector", True),
    _e("DORA_LOCKCHECK_HOLD_MS", "float", "100", "long-hold warning threshold (ms)", True),
    _e("DORA_LOCKCHECK_ALLOW", "str", "", "comma list of suppressed order edges 'a>b'", True),
    _e("DORA_LOCKCHECK_REPORT", "bool", "1", "print the lockcheck report at exit", True),
    # --- daemon / routing / transport ----------------------------------
    _e("DORA_P2P", "bool", "1", "allow direct node-to-node routing", True),
    _e("DORA_SEND_COALESCE", "int", "0", "coalesce small sends (bytes)", True),
    _e("DORA_DAEMON_ADDR", "str", "", "daemon address override for hub nodes"),
    _e("DORA_NODE_CONFIG", "str", "", "spawn-injected node config (set by daemon)", True),
    _e("DORA_RUNTIME_NODE", "bool", "", "marks a runtime-managed operator process (set by daemon)"),
    _e("DORA_CHAOS_ID", "str", "", "dataflow:node tag for chaos targeting (set by daemon)"),
    _e("DORA_TEST_SESSION", "str", "", "test-session mark for orphan cleanup (set by conftest)"),
    _e("DORA_TPU_STATE_DIR", "path", "~/.dora-tpu", "coordinator/daemon state dir"),
    _e("DORA_TPU_CACHE", "path", "~/.cache/dora-tpu", "artifact download cache"),
    # --- ros2 / rtps bridge --------------------------------------------
    _e("DORA_RTPS_PEERS", "str", "", "static RTPS peer list"),
    _e("DORA_RTPS_LEASE_S", "float", "20", "RTPS liveliness lease seconds"),
    _e("DORA_RTPS_ANNOUNCE_S", "float", "5", "RTPS announce interval seconds"),
    # --- serving engine ------------------------------------------------
    _e("DORA_STUB_ENGINE", "bool", "0", "run the CPU stub engine", True),
    _e("DORA_STUB_CYCLE", "str", "", "stub engine canned-token cycle", True),
    _e("DORA_HF_CHECKPOINT", "path", "", "HF checkpoint dir for the real engine"),
    _e("DORA_CHECKPOINT", "path", "", "ops-node checkpoint path"),
    _e("DORA_CHECKPOINT_DIR", "path", "", "engine pool checkpoint/restore dir", True),
    _e("DORA_CHECKPOINT_EVERY", "int", "0", "checkpoint cadence (windows)", True),
    _e("DORA_CHECKPOINT_PAGES", "bool", "0", "include KV pages in checkpoints"),
    _e("DORA_MIGRATE_DIR", "path", "", "live-migration handoff dir", True),
    _e("DORA_BATCH_SLOTS", "int", "8", "continuous-batching slot count", True),
    _e("DORA_MAX_SEQ", "int", "1024", "max sequence length", True),
    _e("DORA_MAX_NEW_TOKENS", "int", "128", "default completion token budget", True),
    _e("DORA_MULTISTEP_K", "int", "8", "fused decode window size K", True),
    _e("DORA_STEP_DELAY_S", "float", "0", "stub engine: modelled device time per decode window (tests)"),
    _e("DORA_PREFILL_CHUNK", "int", "0", "chunked prefill size", True),
    _e("DORA_PAGE_SIZE", "int", "64", "KV page size (tokens)", True),
    _e("DORA_PREFIX_CACHE", "bool", "0", "shared-prefix KV cache", True),
    _e("DORA_PREFIX_CACHE_PAGES", "int", "0", "prefix cache page budget", True),
    _e("DORA_OPENAI_CONCURRENT", "bool", "0", "concurrent OpenAI-server request handling", True),
    # --- qos / slo (descriptor blocks -> spawn env) --------------------
    _e("DORA_QOS_DEFAULT_CLASS", "str", "standard", "default admission QoS class", True),
    _e("DORA_QOS_DEPTH_INTERACTIVE", "int", "", "interactive-class backlog bound", True),
    _e("DORA_QOS_DEPTH_STANDARD", "int", "", "standard-class backlog bound"),
    _e("DORA_QOS_DEPTH_BATCH", "int", "", "batch-class backlog bound"),
    _e("DORA_QOS_SHED_WAIT_MS", "float", "", "shed requests queued longer than this", True),
    _e("DORA_QOS_AGING_S", "float", "", "class aging half-life for anti-starvation", True),
    _e("DORA_QOS_PREEMPT", "bool", "0", "allow higher-class preemption", True),
    _e("DORA_SLO_TTFT_P99_MS", "float", "", "SLO target: p99 time-to-first-token"),
    _e("DORA_SLO_TOKENS_PER_S_MIN", "float", "", "SLO target: min decode throughput"),
    _e("DORA_SLO_QUEUE_DEPTH_MAX", "int", "", "SLO target: max admission queue depth"),
    # --- slo autotuner -------------------------------------------------
    _e("DORA_AUTOTUNE_K", "bool", "0", "SLO-driven window autotuner", True),
    _e("DORA_AUTOTUNE_LADDER", "str", "", "autotuner K ladder", True),
    _e("DORA_AUTOTUNE_INTERVAL_S", "float", "", "autotuner decision interval", True),
    _e("DORA_AUTOTUNE_BURN_WINDOW_S", "float", "", "burn-rate window for autotune", True),
    _e("DORA_AUTOTUNE_HYSTERESIS", "float", "", "autotuner hysteresis factor", True),
    # --- models / ops --------------------------------------------------
    _e("DORA_MESH", "str", "", "device mesh spec for fused pipelines", True),
    _e("DORA_PIPELINE_DEPTH", "int", "2", "fuse pipeline depth", True),
    _e("DORA_FETCH_EVERY", "int", "1", "fused fetch cadence", True),
    _e("DORA_FETCH_LINGER_MS", "float", "0", "fused fetch linger window"),
    _e("DORA_FLASH_ATTENTION", "bool", "0", "flash-attention kernels"),
    _e("DORA_FUSED_DECODE", "bool", "0", "fused decode step"),
    _e("DORA_DECODE_UNROLL", "int", "1", "decode loop unroll factor"),
    _e("DORA_INT8_DECODE", "bool", "0", "int8 weight quantized decode", True),
    _e("DORA_INT8_PURE", "bool", "0", "pure-int8 matmul path"),
    _e("DORA_INT4_DECODE", "bool", "0", "int4 weight quantized decode", True),
    _e("DORA_KV_INT8", "bool", "0", "int8 KV pages with per-page scales",
       True),
    _e("DORA_WEIGHT_BITS", "str", "", "decode weight bits (4 or 8)", True),
    _e("DORA_EP_RANK", "int", "0", "this process's rank in a kimi_k2 expert group (the size is config.json's ep_size)", True),
    _e("DORA_LORA_DIR", "path", "", "LoRA adapter catalog directory", True),
    _e("DORA_LORA_MAX_RESIDENT", "int", "8",
       "resident LoRA adapter slots", True),
    _e("DORA_LORA_RANK", "int", "", "LoRA pool rank override", True),
    _e("DORA_PARAM_DTYPE", "str", "", "parameter dtype override"),
    _e("DORA_SP_IMPL", "str", "", "sequence-parallel impl selector", True),
    _e("DORA_SPEC_DECODE", "bool", "0", "speculative decoding", True),
    _e("DORA_SPEC_K", "int", "4", "speculation depth", True),
    _e("DORA_SPEC_NGRAM", "int", "0", "n-gram draft order", True),
    _e("DORA_SPEC_BODY", "str", "", "draft body spec", True),
    _e("DORA_SPEC_ADAPTIVE", "bool", "0", "adaptive speculation length"),
    _e("DORA_SPEC_WORST_CASE", "bool", "0", "worst-case speculation accounting"),
    _e("DORA_MODEL_SIZE", "str", "", "ops-node model size preset"),
    _e("DORA_MAX_TILES", "int", "", "vision max image tiles"),
    _e("DORA_MAX_SRC", "int", "", "translator max source length"),
    _e("DORA_DETECT_THRESHOLD", "float", "", "detector score threshold"),
    _e("DORA_DETECT_TOPK", "int", "", "detector top-k"),
    _e("DORA_TOKENIZER", "path", "", "tokenizer path override"),
    _e("DORA_PROMPT", "str", "", "ops-node prompt override"),
    _e("DORA_TTS_STYLE", "str", "", "TTS style preset"),
    # --- distributed jax ----------------------------------------------
    _e("DORA_JAX_COORDINATOR", "str", "", "jax.distributed coordinator addr"),
    _e("DORA_JAX_NUM_PROCESSES", "int", "", "jax.distributed process count"),
    _e("DORA_JAX_PROCESS_ID", "int", "", "jax.distributed process id"),
    # --- bench ---------------------------------------------------------
    _e("DORA_BENCH_TRIALS", "int", "3", "bench_serving trial count"),
    _e("DORA_BENCH_QOS_STREAMS", "int", "", "bench_serving QoS stream mix"),
    _e("DORA_BENCH_PREFIX_STREAMS", "int", "", "bench_serving shared-prefix streams"),
))

#: Non-env ``DORA_`` identifiers that legitimately appear in docs/source:
#: C enum names in the embedded native source and README prose.
ALLOWED_NON_ENV_PREFIXES = ("DORA_EVENT_", "DORA_OP_")


def is_registered(name: str) -> bool:
    return name in REGISTRY


def _prefix_ok(token: str) -> bool:
    """A token like ``DORA_SLO_`` (an f-string head or README family
    shorthand) is fine when registered names extend it."""
    return token.endswith("_") and any(
        n.startswith(token) for n in REGISTRY
    )


def _allowlisted(token: str) -> bool:
    return any(token.startswith(p) for p in ALLOWED_NON_ENV_PREFIXES)


# ---------------------------------------------------------------------------
# lint: every DORA_* env read / literal is declared
# ---------------------------------------------------------------------------


def _env_read_name(node: ast.AST, consts: dict[str, str]) -> str | None:
    """Name read by ``os.environ.get/.pop/.setdefault``, ``os.environ[..]``
    or ``os.getenv`` — literal or via a module-level string constant."""
    def resolve(arg):
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value
        if isinstance(arg, ast.Name):
            return consts.get(arg.id)
        return None

    if isinstance(node, ast.Call):
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr in ("get", "pop", "setdefault")
            and isinstance(f.value, ast.Attribute)
            and f.value.attr == "environ"
        ) or (isinstance(f, ast.Attribute) and f.attr == "getenv"):
            if node.args:
                return resolve(node.args[0])
    elif isinstance(node, ast.Subscript):
        v = node.value
        if isinstance(v, ast.Attribute) and v.attr == "environ":
            return resolve(node.slice)
    return None


def lint_env_reads(package_root: str | Path = "dora_tpu") -> list[Finding]:
    out: list[Finding] = []
    for path in sorted(Path(package_root).rglob("*.py")):
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue
        consts = {
            t.id: s.value.value
            for s in tree.body
            if isinstance(s, ast.Assign) and isinstance(s.value, ast.Constant)
            and isinstance(s.value.value, str)
            for t in s.targets
            if isinstance(t, ast.Name)
        }
        read_nodes: set[int] = set()
        for node in ast.walk(tree):
            name = _env_read_name(node, consts)
            if name is None:
                continue
            # Remember the literal-arg node so the generic literal sweep
            # below doesn't double-report the same site.
            if isinstance(node, ast.Call) and node.args:
                read_nodes.add(id(node.args[0]))
            elif isinstance(node, ast.Subscript):
                read_nodes.add(id(node.slice))
            if name.startswith("DORA_") and not is_registered(name):
                out.append(Finding(
                    "envreg", "env-undeclared", "error",
                    f"{path}:{node.lineno}",
                    f"env read of {name!r} is not declared in "
                    "dora_tpu.analysis.envreg.REGISTRY",
                ))
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and _TOKEN.fullmatch(node.value)
            ):
                continue
            if id(node) in read_nodes:
                continue
            tok = node.value
            if is_registered(tok) or _prefix_ok(tok) or _allowlisted(tok):
                continue
            out.append(Finding(
                "envreg", "env-unregistered-literal", "error",
                f"{path}:{node.lineno}",
                f"DORA_* literal {tok!r} is neither a registered env var "
                "nor an allowlisted identifier",
            ))
    return out


# ---------------------------------------------------------------------------
# lint: README env tables <-> registry
# ---------------------------------------------------------------------------


def lint_readme(readme_path: str | Path = "README.md") -> list[Finding]:
    out: list[Finding] = []
    path = Path(readme_path)
    if not path.exists():
        return [Finding("envreg", "env-readme-unknown", "error", str(path),
                        "README not found")]
    text = path.read_text()
    tokens = set(_TOKEN.findall(text))
    for tok in sorted(tokens):
        if is_registered(tok) or _prefix_ok(tok) or _allowlisted(tok):
            continue
        out.append(Finding(
            "envreg", "env-readme-unknown", "error", str(path),
            f"README mentions {tok!r}, which is not a registered env var",
        ))
    for var in REGISTRY.values():
        if var.readme and var.name not in tokens:
            out.append(Finding(
                "envreg", "env-readme-missing", "error", str(path),
                f"{var.name} is marked readme=True but absent from the "
                "README env tables",
            ))
    return out


def lint(package_root: str | Path = "dora_tpu",
         readme_path: str | Path = "README.md") -> list[Finding]:
    return lint_env_reads(package_root) + lint_readme(readme_path)
