"""Deploy-config consistency: the checks a docker build would catch.

This environment has no container runtime (ROADMAP "Operations"), so the
images cannot be built here; these tests pin everything statically
verifiable instead: dockerfile COPY sources exist, entrypoints name real
console scripts, the k8s manifests wire the ports and env vars the code
actually listens on, and the two tiers' service DNS names line up --
the class of mistakes the reference's guide debugs by kubectl-eye
(reference guide.md:461-581).
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize

import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEPLOY = os.path.join(REPO, "deploy")


def _read(path):
    with open(path) as f:
        return f.read()


def _yaml_docs(path):
    return [d for d in yaml.safe_load_all(_read(path)) if d]


def test_dockerfile_copy_sources_exist():
    for name in ("gateway.dockerfile", "model-server.dockerfile"):
        text = _read(os.path.join(DEPLOY, name))
        for m in re.finditer(r"^\s*COPY\s+(?:--[\w=]+\s+)*(\S+)\s+\S+", text, re.M):
            src = m.group(1)
            if src == "models":
                # Build-time artifact: kdlt-export produces it right before
                # docker build, the same way the reference bakes its
                # SavedModel (reference tf-serving.dockerfile:5).
                continue
            assert os.path.exists(os.path.join(REPO, src)), (
                f"{name}: COPY source {src!r} does not exist in the build context"
            )


# --- no document names a file that is not there ------------------------------

# Names that are not files of this repository, each with what it is.
_NOT_OURS = {
    "test.py": "the reference repository's client script",
    "convert.py": "the reference repository's Keras -> SavedModel script",
    "spec.json": "a file of the artifact layout, written by the exporter",
    "metadata.json": "a file of the artifact layout, written by the exporter",
    "bundle.json": "an example name for an incident bundle copied off a pod",
}
_SKIP_DIRS = {"__pycache__", "build", "tfs_gen", "chiprun_out"}
_PROSE_SUFFIXES = (".py", ".md", ".yaml", ".yml", ".dockerfile", ".toml", ".cc", ".h")
_FILE_NAME = re.compile(
    r"(?<![\w./*<>{}$-])((?:[\w.-]+/)*[\w.-]+\.(?:py|json))(?![\w*])"
)
_PACKAGE = os.path.join(REPO, "kubernetes_deep_learning_tpu")


def _walk(top):
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = [
            d for d in dirnames if d not in _SKIP_DIRS and not d.startswith(".")
        ]
        for name in filenames:
            yield os.path.join(dirpath, name)


def _prose(path):
    """What a file says to its reader: all of a document or manifest, the
    comments and docstrings of a Python file (its code and string literals
    are data: fixture names, dictionary keys, attribute reads)."""
    text = _read(path)
    if not path.endswith(".py"):
        return text
    parts = [
        tok.string
        for tok in tokenize.generate_tokens(io.StringIO(text).readline)
        if tok.type == tokenize.COMMENT
    ]
    for node in ast.walk(ast.parse(text)):
        if isinstance(
            node, (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            parts.append(ast.get_docstring(node, clean=False) or "")
    return "\n".join(parts)


@pytest.mark.parametrize(
    "source",
    [
        "README.md", "GUIDE.md", "exp/README.md",
        ".claude/skills/verify/SKILL.md", "deploy",
        "kubernetes_deep_learning_tpu", "tools", "tests",
    ],
)
def test_documents_name_only_files_that_exist(source):
    """Every ``*.py`` or ``*.json`` a document, manifest, comment or docstring
    names is in the tree: a bare name is some file's name, a path resolves
    from the checkout or from the package.  ROADMAP.md, CHANGES.md and PERF.md
    are history and are not read."""
    basenames = {os.path.basename(f) for f in _walk(REPO)}
    top = os.path.join(REPO, source)
    files = [top] if os.path.isfile(top) else [
        f for f in _walk(top) if f.endswith(_PROSE_SUFFIXES)
    ]
    assert files, source
    missing = set()
    for path in files:
        for name in _FILE_NAME.findall(_prose(path)):
            if "/" not in name:
                if name not in basenames and name not in _NOT_OURS:
                    missing.add((os.path.relpath(path, REPO), name))
                continue
            first = name.split("/")[0]
            roots = [r for r in (REPO, _PACKAGE) if os.path.isdir(os.path.join(r, first))]
            if roots and not any(os.path.exists(os.path.join(r, name)) for r in roots):
                missing.add((os.path.relpath(path, REPO), name))
    assert not missing, f"named but not in the tree: {sorted(missing)}"


def test_dockerfile_entrypoints_are_real_console_scripts():
    # tomllib is stdlib only on 3.11+; requires-python allows 3.10.
    tomllib = pytest.importorskip("tomllib")

    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        scripts = set(tomllib.load(f)["project"]["scripts"])
    for name in ("gateway.dockerfile", "model-server.dockerfile"):
        text = _read(os.path.join(DEPLOY, name))
        # Command names only: "kdlt-xla" inside /var/cache/kdlt-xla is a
        # path component, not a console script.
        used = set(re.findall(r"(?<![\w/-])kdlt-[\w-]+", text))
        missing = {u for u in used if u not in scripts and not u.startswith("kdlt-models")}
        assert not missing, f"{name} invokes unknown scripts {missing}"


def test_k8s_ports_and_env_wiring():
    from kubernetes_deep_learning_tpu.serving.gateway import (
        DEFAULT_PORT as GATEWAY_PORT,
        SERVING_HOST_ENV,
    )
    from kubernetes_deep_learning_tpu.serving.model_server import (
        DEFAULT_PORT as MODEL_PORT,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (model_svc,) = _yaml_docs(os.path.join(k8s, "model-server-service.yaml"))
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    (gw_svc,) = _yaml_docs(os.path.join(k8s, "gateway-service.yaml"))

    model_container = model_dep["spec"]["template"]["spec"]["containers"][0]
    assert any(
        p["containerPort"] == MODEL_PORT for p in model_container["ports"]
    ), "model-server container must expose its default port"
    assert model_svc["spec"]["ports"][0]["port"] == MODEL_PORT

    gw_container = gw_dep["spec"]["template"]["spec"]["containers"][0]
    assert any(p["containerPort"] == GATEWAY_PORT for p in gw_container["ports"])
    env = {e["name"]: e.get("value", "") for e in gw_container.get("env", [])}
    assert SERVING_HOST_ENV in env, (
        f"gateway Deployment must set {SERVING_HOST_ENV} (the reference's "
        "TF_SERVING_HOST convention)"
    )
    # The discovery value must point at the model Service's DNS name + port.
    svc_name = model_svc["metadata"]["name"]
    assert env[SERVING_HOST_ENV].startswith(svc_name), env[SERVING_HOST_ENV]
    assert env[SERVING_HOST_ENV].endswith(str(MODEL_PORT))
    # LoadBalancer ingress fronts the gateway (reference serving-gateway-service.yaml:8-11)
    assert gw_svc["spec"]["type"] == "LoadBalancer"
    assert gw_svc["spec"]["ports"][0]["targetPort"] == GATEWAY_PORT


def test_k8s_model_server_compile_cache_volume():
    """The persistent-compile-cache wiring must be complete end to end:
    env var -> mount -> volume (utils/compilecache.py; a restarted
    container re-reads compiled bucket programs instead of re-paying ~10
    min of warmup)."""
    from kubernetes_deep_learning_tpu.utils.compilecache import ENV_VAR

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    pod = model_dep["spec"]["template"]["spec"]
    container = pod["containers"][0]
    env = {e["name"]: e.get("value", "") for e in container.get("env", [])}
    assert ENV_VAR in env, "model server must point the XLA cache at a volume"
    cache_path = env[ENV_VAR]
    mounts = {m["name"]: m["mountPath"] for m in container.get("volumeMounts", [])}
    assert cache_path in mounts.values(), (
        f"{ENV_VAR}={cache_path} must be a mounted volume, not container-"
        "ephemeral filesystem (the whole point is surviving restarts)"
    )
    mount_name = next(n for n, p in mounts.items() if p == cache_path)
    assert any(v["name"] == mount_name for v in pod.get("volumes", []))
    # ADVICE r4: steady-state readiness must evict an unhealthy pod from
    # the endpoint pool quickly; the warmup budget lives on startupProbe.
    assert container["readinessProbe"]["failureThreshold"] <= 5
    assert container["startupProbe"]["failureThreshold"] >= 60


def test_k8s_and_compose_drain_semantics():
    """The graceful-drain wiring (serving.admission): SIGTERM-driven drain
    needs (a) a preStop sleep so the endpoint controller removes the pod
    from the Service BEFORE admission stops, and (b) a termination grace
    period that covers preStop + the KDLT_DRAIN_TIMEOUT_S default (25 s) --
    otherwise kubelet SIGKILLs mid-drain and in-flight batches die anyway."""
    from kubernetes_deep_learning_tpu.serving.admission.controller import (
        DEFAULT_DRAIN_TIMEOUT_S,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    for fname in ("gateway-deployment.yaml", "model-server-deployment.yaml"):
        (dep,) = _yaml_docs(os.path.join(k8s, fname))
        pod = dep["spec"]["template"]["spec"]
        container = pod["containers"][0]
        grace = pod.get("terminationGracePeriodSeconds", 30)
        pre_stop = container.get("lifecycle", {}).get("preStop")
        assert pre_stop is not None, f"{fname}: no preStop hook"
        sleep_s = float(pre_stop["exec"]["command"][-1])
        assert grace >= sleep_s + DEFAULT_DRAIN_TIMEOUT_S, (
            f"{fname}: grace {grace}s cannot cover preStop {sleep_s}s + "
            f"drain {DEFAULT_DRAIN_TIMEOUT_S}s"
        )
        # Drain flips /readyz, so readiness MUST probe /readyz for the
        # endpoint eviction half of the story to exist at all.
        assert container["readinessProbe"]["httpGet"]["path"] == "/readyz", fname

    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    for name, svc in compose["services"].items():
        grace = svc.get("stop_grace_period", "10s")
        assert float(str(grace).rstrip("s")) >= DEFAULT_DRAIN_TIMEOUT_S, (
            f"compose service {name}: stop_grace_period {grace} cannot cover "
            f"the {DEFAULT_DRAIN_TIMEOUT_S}s drain budget"
        )


def test_k8s_model_tier_replicated_for_failover():
    """The serving-path fault-tolerance wiring (serving/upstream.py): the
    model tier runs >= 2 replicas behind a headless Service, the gateway
    discovers them by re-resolving that Service's DNS name live
    (KDLT_POOL_RESOLVE_S dynamic membership -- an HPA scale-up changes the
    upstream pool with NO gateway redeploy), and the hedge/probe knobs
    are set."""
    from kubernetes_deep_learning_tpu.serving.gateway import SERVING_HOST_ENV
    from kubernetes_deep_learning_tpu.serving.model_server import (
        DEFAULT_PORT as MODEL_PORT,
    )
    from kubernetes_deep_learning_tpu.serving.upstream import (
        HEDGE_DELAY_ENV,
        POOL_RESOLVE_ENV,
        PROBE_INTERVAL_ENV,
        parse_hosts,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (model_svc,) = _yaml_docs(os.path.join(k8s, "model-server-service.yaml"))
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))

    assert model_dep["spec"]["replicas"] >= 2, (
        "failover needs a survivor: the model tier must run >= 2 replicas"
    )
    # Stable per-replica DNS requires a StatefulSet behind a headless Service
    # -- and headless is what makes the Service name resolve to EVERY ready
    # pod address, which is what the gateway's re-resolver diffs.
    assert model_dep["kind"] == "StatefulSet"
    assert model_dep["spec"]["serviceName"] == model_svc["metadata"]["name"]
    assert model_svc["spec"].get("clusterIP") is None or (
        model_svc["spec"]["clusterIP"] == "None"
    )

    gw_container = gw_dep["spec"]["template"]["spec"]["containers"][0]
    env = {e["name"]: e.get("value", "") for e in gw_container.get("env", [])}
    hosts = parse_hosts(env[SERVING_HOST_ENV])
    svc_name = model_svc["metadata"]["name"]
    # Dynamic membership: the gateway names the headless Service itself
    # (one name resolving to the whole fleet), not a static per-pod list
    # that every scale event would have to edit.
    assert len(hosts) == 1, (
        f"{SERVING_HOST_ENV} should name the headless Service once and let "
        f"re-resolution track the fleet, got {hosts}"
    )
    assert hosts[0].startswith(f"{svc_name}."), hosts[0]
    assert hosts[0].endswith(str(MODEL_PORT)), hosts[0]
    assert float(env[POOL_RESOLVE_ENV]) > 0, (
        "dynamic membership wired off: a scale-up would never join the pool"
    )
    assert float(env[HEDGE_DELAY_ENV]) > 0, "hedging must be wired on"
    assert float(env[PROBE_INTERVAL_ENV]) > 0, "active probing must be on"

    # Readiness tuned for failover: with a survivor carrying the tier,
    # eviction latency IS failover latency -- a dead replica must leave the
    # endpoint pool within a few seconds.
    model_container = model_dep["spec"]["template"]["spec"]["containers"][0]
    probe = model_container["readinessProbe"]
    assert probe["periodSeconds"] * probe["failureThreshold"] <= 6, (
        "readiness eviction must complete within a few seconds for failover"
    )


def test_compose_has_second_model_replica_wired_for_failover():
    """docker-compose: two model-server replicas, the gateway's
    KDLT_SERVING_HOST listing both, hedging configured -- the topology
    tests/test_failover.py kills a replica of."""
    from kubernetes_deep_learning_tpu.serving.gateway import SERVING_HOST_ENV
    from kubernetes_deep_learning_tpu.serving.upstream import (
        HEDGE_DELAY_ENV,
        parse_hosts,
    )

    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    services = compose["services"]
    gw_env = services["gateway"]["environment"]
    hosts = parse_hosts(str(gw_env[SERVING_HOST_ENV]))
    assert len(hosts) >= 2, "gateway must be wired with a replica list"
    model_services = [h.split(":")[0] for h in hosts]
    for name in model_services:
        assert name in services, f"replica list names unknown service {name!r}"
        # Every listed replica is a model-server build with a healthcheck
        # (the gateway's depends_on gates on it).
        assert "model-server" in services[name]["build"]["dockerfile"]
        assert "healthcheck" in services[name]
        assert name in services["gateway"]["depends_on"]
    assert float(gw_env[HEDGE_DELAY_ENV]) > 0


def test_prometheus_scrape_annotations():
    """Observability wiring (ISSUE 4 satellite): both tiers' pod templates
    carry the prometheus.io scrape annotations, pointed at /metrics on the
    port the container actually serves; the compose topology carries the
    equivalent labels so a docker_sd-configured Prometheus discovers the
    local stack the same way."""
    from kubernetes_deep_learning_tpu.serving.gateway import (
        DEFAULT_PORT as GATEWAY_PORT,
    )
    from kubernetes_deep_learning_tpu.serving.model_server import (
        DEFAULT_PORT as MODEL_PORT,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    for fname, port in (
        ("gateway-deployment.yaml", GATEWAY_PORT),
        ("model-server-deployment.yaml", MODEL_PORT),
    ):
        (dep,) = _yaml_docs(os.path.join(k8s, fname))
        tmpl = dep["spec"]["template"]["metadata"]
        ann = tmpl.get("annotations", {})
        assert ann.get("prometheus.io/scrape") == "true", fname
        assert ann.get("prometheus.io/path") == "/metrics", fname
        assert ann.get("prometheus.io/port") == str(port), fname
        # The advertised scrape port must be one the container exposes.
        container = dep["spec"]["template"]["spec"]["containers"][0]
        assert any(
            p["containerPort"] == port for p in container["ports"]
        ), fname

    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    for name, svc in compose["services"].items():
        labels = svc.get("labels", {})
        assert labels.get("prometheus.io/scrape") == "true", (
            f"compose service {name!r} missing scrape labels"
        )
        assert labels.get("prometheus.io/path") == "/metrics", name


def test_deploy_wires_structured_logs_and_profile_dir():
    """The tracing/observability env wiring: JSON request logs on both
    tiers (k8s + compose), and the model tier's KDLT_PROFILE_DIR pointed
    at a mounted volume so /debug/profile captures survive and can be
    copied out."""
    from kubernetes_deep_learning_tpu.serving.model_server import (
        PROFILE_DIR_ENV,
    )
    from kubernetes_deep_learning_tpu.serving.tracing import LOG_FORMAT_ENV

    k8s = os.path.join(DEPLOY, "k8s")
    for fname in ("gateway-deployment.yaml", "model-server-deployment.yaml"):
        (dep,) = _yaml_docs(os.path.join(k8s, fname))
        container = dep["spec"]["template"]["spec"]["containers"][0]
        env = {e["name"]: e.get("value", "") for e in container.get("env", [])}
        assert env.get(LOG_FORMAT_ENV) == "json", fname

    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    container = model_dep["spec"]["template"]["spec"]["containers"][0]
    env = {e["name"]: e.get("value", "") for e in container.get("env", [])}
    profile_dir = env[PROFILE_DIR_ENV]
    mounts = [m["mountPath"] for m in container.get("volumeMounts", [])]
    assert any(profile_dir.startswith(m) for m in mounts), (
        f"{PROFILE_DIR_ENV}={profile_dir} must live under a mounted volume"
    )

    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    for name, svc in compose["services"].items():
        assert str(svc.get("environment", {}).get(LOG_FORMAT_ENV)) == "json", (
            f"compose service {name!r} missing {LOG_FORMAT_ENV}=json"
        )


def test_deploy_wires_crosshost_pipeline_envs():
    """Cross-host dispatch pipelining (ISSUE 5): the model tier carries the
    fleet-wide in-flight budget and follower stall-detection envs in both
    deploy targets, with values the code would actually accept (every
    process of a fleet must agree on the depth, so it must come from the
    manifest, not per-pod defaults)."""
    from kubernetes_deep_learning_tpu.parallel.crosshost import (
        XH_PIPELINE_DEPTH_ENV,
        XH_STALL_FLOOR_S_ENV,
        XH_STALL_MULTIPLE_ENV,
        resolve_xh_pipeline_depth,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    container = model_dep["spec"]["template"]["spec"]["containers"][0]
    env = {e["name"]: e.get("value", "") for e in container.get("env", [])}
    for name in (
        XH_PIPELINE_DEPTH_ENV, XH_STALL_FLOOR_S_ENV, XH_STALL_MULTIPLE_ENV
    ):
        assert name in env, f"model tier must set {name}"
    depth = resolve_xh_pipeline_depth(int(env[XH_PIPELINE_DEPTH_ENV]))
    assert depth == int(env[XH_PIPELINE_DEPTH_ENV]) >= 1
    assert float(env[XH_STALL_FLOOR_S_ENV]) > 0, "stall detection wired off"
    assert float(env[XH_STALL_MULTIPLE_ENV]) >= 1.0

    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    services = compose["services"]
    replicas = [
        name for name, svc in services.items()
        if isinstance(svc.get("build"), dict)
        and "model-server" in svc["build"].get("dockerfile", "")
    ]
    assert len(replicas) >= 2
    depths = set()
    for name in replicas:
        env = services[name].get("environment", {})
        for var in (
            XH_PIPELINE_DEPTH_ENV, XH_STALL_FLOOR_S_ENV, XH_STALL_MULTIPLE_ENV
        ):
            assert var in env, f"compose service {name!r} missing {var}"
        depths.add(str(env[XH_PIPELINE_DEPTH_ENV]))
    # The budget is a fleet-wide protocol parameter: replicas must agree.
    assert len(depths) == 1, f"replicas disagree on the depth: {depths}"


def test_multimodel_scheduler_and_default_model_wiring():
    """Multi-model serving (ISSUE 6): the model tier carries the unified
    scheduler's policy + per-model weight envs in BOTH deploy targets with
    values the code accepts, every model-tier replica agrees (the gateway
    fails over between them -- a replica on a different policy serves a
    different latency profile), the gateway's default-model env matches
    between k8s and compose, and the default model's weight is pinned so a
    second baked-in model cannot silently dilute its share."""
    from kubernetes_deep_learning_tpu.runtime.scheduler import (
        SCHED_POLICY_ENV,
        SCHED_WEIGHTS_ENV,
        resolve_policy,
        resolve_weights,
    )
    from kubernetes_deep_learning_tpu.serving.gateway import MODEL_ENV

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    container = model_dep["spec"]["template"]["spec"]["containers"][0]
    k8s_env = {e["name"]: e.get("value", "") for e in container.get("env", [])}
    assert SCHED_POLICY_ENV in k8s_env, "model tier must pin the policy"
    assert resolve_policy(k8s_env[SCHED_POLICY_ENV]) == k8s_env[SCHED_POLICY_ENV]
    assert SCHED_WEIGHTS_ENV in k8s_env
    k8s_weights = resolve_weights(k8s_env[SCHED_WEIGHTS_ENV])
    assert k8s_weights, "weights env must parse to at least one entry"

    gw_env = {
        e["name"]: e.get("value", "")
        for e in gw_dep["spec"]["template"]["spec"]["containers"][0]["env"]
    }
    default_model = gw_env[MODEL_ENV]
    assert default_model, "gateway must pin the default model"
    assert default_model in k8s_weights, (
        "the default model's scheduling weight must be pinned explicitly"
    )

    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    services = compose["services"]
    assert str(services["gateway"]["environment"][MODEL_ENV]) == default_model, (
        "k8s and compose must agree on the default model"
    )
    replicas = [
        name for name, svc in services.items()
        if isinstance(svc.get("build"), dict)
        and "model-server" in svc["build"].get("dockerfile", "")
    ]
    assert len(replicas) >= 2
    for name in replicas:
        env = services[name].get("environment", {})
        assert str(env.get(SCHED_POLICY_ENV)) == k8s_env[SCHED_POLICY_ENV], (
            f"compose replica {name!r} disagrees with k8s on the policy"
        )
        assert resolve_weights(str(env.get(SCHED_WEIGHTS_ENV))) == k8s_weights, (
            f"compose replica {name!r} disagrees with k8s on the weights"
        )


def test_gateway_cache_envs_agree_across_k8s_and_compose():
    """The response-cache wiring (ISSUE 8): the gateway carries the
    KDLT_CACHE_* envs in BOTH deploy targets with values the code accepts,
    and the two topologies agree -- a compose stack used to rehearse a
    k8s rollout must exhibit the same caching behavior (hit ratios,
    staleness window, memory budget)."""
    from kubernetes_deep_learning_tpu.serving.cache import (
        CACHE_ENV,
        MAX_MB_ENV,
        TTL_ENV,
        cache_enabled,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    gw_container = gw_dep["spec"]["template"]["spec"]["containers"][0]
    k8s_env = {
        e["name"]: str(e.get("value", "")) for e in gw_container["env"]
    }
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    compose_env = {
        k: str(v)
        for k, v in compose["services"]["gateway"]["environment"].items()
    }
    for var in (CACHE_ENV, TTL_ENV, MAX_MB_ENV):
        assert var in k8s_env, f"k8s gateway must set {var}"
        assert var in compose_env, f"compose gateway must set {var}"
        assert k8s_env[var] == compose_env[var], (
            f"{var} disagrees: k8s={k8s_env[var]!r} "
            f"compose={compose_env[var]!r}"
        )
    # The values must parse as a usable configuration: cache enabled, a
    # positive staleness bound, a positive byte budget.
    os.environ[CACHE_ENV] = k8s_env[CACHE_ENV]
    try:
        assert cache_enabled() is True, "deploys must not ship the kill switch"
    finally:
        del os.environ[CACHE_ENV]
    assert float(k8s_env[TTL_ENV]) > 0, "TTL wired off"
    assert float(k8s_env[MAX_MB_ENV]) > 0, "byte budget wired off"


def test_quant_envs_agree_across_k8s_and_compose():
    """The full-int8 serving wiring (ISSUE 9): the model tier carries
    KDLT_QUANT_TOL + KDLT_QUANT_SCHEME in BOTH deploy targets (and on both
    compose replicas) with values the code accepts, and every copy agrees
    -- a replica with a looser tolerance bound would activate a w8a8
    program its siblings refused, and the gateway fails over between
    them."""
    from kubernetes_deep_learning_tpu.ops.quantize import (
        QUANT_SCHEME_ENV,
        QUANT_TOL_ENV,
        resolve_quant_tol,
        resolve_scheme_override,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (container,) = model_dep["spec"]["template"]["spec"]["containers"]
    k8s_env = {e["name"]: str(e.get("value", "")) for e in container["env"]}
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    envs = {"k8s/model-server": k8s_env}
    for svc in ("model-server", "model-server-b"):
        envs[f"compose/{svc}"] = {
            k: str(v)
            for k, v in compose["services"][svc]["environment"].items()
        }
    for var in (QUANT_TOL_ENV, QUANT_SCHEME_ENV):
        values = {where: env.get(var) for where, env in envs.items()}
        assert all(v is not None for v in values.values()), (
            f"{var} missing from some model tier: {values}"
        )
        assert len(set(values.values())) == 1, (
            f"{var} disagrees across the model tiers: {values}"
        )
    # The values must parse as a usable configuration through the same
    # resolvers the engine uses.
    tol = float(k8s_env[QUANT_TOL_ENV])
    assert 0.0 < tol < 1.0, "tolerance gate wired to a nonsense bound"
    os.environ[QUANT_TOL_ENV] = k8s_env[QUANT_TOL_ENV]
    os.environ[QUANT_SCHEME_ENV] = k8s_env[QUANT_SCHEME_ENV]
    try:
        assert resolve_quant_tol() == tol
        assert resolve_scheme_override() == "auto", (
            "deploys must not ship the weight-only rollback knob engaged"
        )
    finally:
        del os.environ[QUANT_TOL_ENV]
        del os.environ[QUANT_SCHEME_ENV]


def test_mesh_env_agrees_across_k8s_and_compose():
    """The model-parallel mesh wiring (ISSUE 16): KDLT_MESH_MODEL_PARALLEL
    rides on BOTH deploy targets (and on both compose replicas) with a
    value the resolver accepts, and every copy agrees -- the gateway
    hedges between replicas, and a pair disagreeing on mesh layout would
    serve different latency/memory profiles under the same artifact."""
    from kubernetes_deep_learning_tpu.serving.model_server import (
        MESH_MODEL_PARALLEL_ENV,
        resolve_mesh_model_parallel,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (container,) = model_dep["spec"]["template"]["spec"]["containers"]
    k8s_env = {e["name"]: str(e.get("value", "")) for e in container["env"]}
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    envs = {"k8s/model-server": k8s_env}
    for svc in ("model-server", "model-server-b"):
        envs[f"compose/{svc}"] = {
            k: str(v)
            for k, v in compose["services"][svc]["environment"].items()
        }
    values = {where: env.get(MESH_MODEL_PARALLEL_ENV) for where, env in envs.items()}
    assert all(v is not None for v in values.values()), (
        f"{MESH_MODEL_PARALLEL_ENV} missing from some model tier: {values}"
    )
    assert len(set(values.values())) == 1, (
        f"{MESH_MODEL_PARALLEL_ENV} disagrees across the model tiers: {values}"
    )
    # The value must parse through the same resolver the server uses, and
    # the CLI flag must still win over it.
    os.environ[MESH_MODEL_PARALLEL_ENV] = k8s_env[MESH_MODEL_PARALLEL_ENV]
    try:
        mp = resolve_mesh_model_parallel()
        assert mp >= 1, "mesh knob wired to a nonsense degree"
        assert resolve_mesh_model_parallel(explicit=4) == 4
    finally:
        del os.environ[MESH_MODEL_PARALLEL_ENV]


def test_isolation_and_brownout_envs_agree_across_k8s_and_compose():
    """The tenant-isolation wiring (ISSUE 12): per-model admission budgets
    on EVERY tier copy (a replica pair disagreeing on partitioning would
    shed different tenants under the same overload), and the brownout
    ladder + SWR window on both gateway deploys, with values the code's
    own resolvers accept."""
    from kubernetes_deep_learning_tpu.serving.admission.brownout import (
        BROWNOUT_ENV,
        BURN_ENTER_ENV,
        BURN_EXIT_ENV,
        brownout_enabled,
    )
    from kubernetes_deep_learning_tpu.serving.admission.limiter import (
        BUDGETS_ENV,
        env_budgets,
    )
    from kubernetes_deep_learning_tpu.serving.cache import SWR_ENV, TTL_ENV

    k8s = os.path.join(DEPLOY, "k8s")
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    (gw_container,) = gw_dep["spec"]["template"]["spec"]["containers"]
    k8s_gw = {e["name"]: str(e.get("value", "")) for e in gw_container["env"]}
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (model_container,) = model_dep["spec"]["template"]["spec"]["containers"]
    k8s_model = {
        e["name"]: str(e.get("value", "")) for e in model_container["env"]
    }
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))

    def compose_env(svc):
        return {
            k: str(v)
            for k, v in compose["services"][svc]["environment"].items()
        }

    # Budgets: present and agreeing on every copy of every tier.
    budget_copies = {
        "k8s/gateway": k8s_gw.get(BUDGETS_ENV),
        "k8s/model-server": k8s_model.get(BUDGETS_ENV),
        "compose/gateway": compose_env("gateway").get(BUDGETS_ENV),
        "compose/model-server": compose_env("model-server").get(BUDGETS_ENV),
        "compose/model-server-b": compose_env("model-server-b").get(BUDGETS_ENV),
    }
    assert all(v is not None for v in budget_copies.values()), budget_copies
    assert len(set(budget_copies.values())) == 1, budget_copies
    # ... and the shipped value ENABLES partitioning through the code's
    # own resolver (None would be the legacy shared limiter).
    os.environ[BUDGETS_ENV] = k8s_model[BUDGETS_ENV]
    try:
        assert env_budgets() is not None, "deploys ship the legacy limiter"
    finally:
        del os.environ[BUDGETS_ENV]

    # Brownout ladder + SWR: both gateway deploys, agreeing.
    compose_gw = compose_env("gateway")
    for var in (BROWNOUT_ENV, BURN_ENTER_ENV, BURN_EXIT_ENV, SWR_ENV):
        assert var in k8s_gw, f"k8s gateway must set {var}"
        assert var in compose_gw, f"compose gateway must set {var}"
        assert k8s_gw[var] == compose_gw[var], (
            f"{var} disagrees: k8s={k8s_gw[var]!r} compose={compose_gw[var]!r}"
        )
    os.environ[BROWNOUT_ENV] = k8s_gw[BROWNOUT_ENV]
    try:
        assert brownout_enabled() is True, "deploys ship the kill switch"
    finally:
        del os.environ[BROWNOUT_ENV]
    enter = float(k8s_gw[BURN_ENTER_ENV])
    exit_ = float(k8s_gw[BURN_EXIT_ENV])
    assert 0.0 < exit_ < enter, (
        "hysteresis requires exit strictly inside (0, enter)"
    )
    # The SWR window only matters under brownout; it must be positive and
    # it bounds worst-case staleness to TTL + SWR, so keep it sane vs TTL.
    assert float(k8s_gw[SWR_ENV]) > 0, "SWR wired off"
    assert float(k8s_gw[SWR_ENV]) <= 10 * float(k8s_gw[TTL_ENV])


def test_gateway_negative_cache_ttl_wired():
    """Negative caching (ROADMAP cache follow-on #1): both gateway deploys
    carry KDLT_CACHE_NEG_TTL_S, agreeing, positive (the feature is ON in
    production), and within the positive TTL (a negative entry must never
    outlive a positive one)."""
    from kubernetes_deep_learning_tpu.serving.cache import NEG_TTL_ENV, TTL_ENV

    k8s = os.path.join(DEPLOY, "k8s")
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    (container,) = gw_dep["spec"]["template"]["spec"]["containers"]
    k8s_env = {e["name"]: str(e.get("value", "")) for e in container["env"]}
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    compose_env = {
        k: str(v)
        for k, v in compose["services"]["gateway"]["environment"].items()
    }
    assert NEG_TTL_ENV in k8s_env and NEG_TTL_ENV in compose_env
    assert k8s_env[NEG_TTL_ENV] == compose_env[NEG_TTL_ENV]
    neg = float(k8s_env[NEG_TTL_ENV])
    assert 0 < neg <= float(k8s_env[TTL_ENV])


def test_model_server_hpa_scales_on_minted_serving_signals():
    """The model-tier HPA (ROADMAP multi-model gap #4) must scale on metric
    names the serving path actually mints: every metric named in the HPA
    must appear as a literal series name in utils/metrics.py (the single
    minting point check_metrics.py enforces), and the scale target must be
    the StatefulSet the deployment manifest declares."""
    k8s = os.path.join(DEPLOY, "k8s")
    (hpa,) = _yaml_docs(os.path.join(k8s, "model-server-hpa.yaml"))
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))

    ref = hpa["spec"]["scaleTargetRef"]
    assert ref["kind"] == model_dep["kind"]
    assert ref["name"] == model_dep["metadata"]["name"]

    metrics_src = _read(os.path.join(
        REPO, "kubernetes_deep_learning_tpu", "utils", "metrics.py"
    ))
    names = [
        m["pods"]["metric"]["name"]
        for m in hpa["spec"]["metrics"] if m["type"] == "Pods"
    ]
    assert "kdlt_slo_burn_rate" in names, (
        "the HPA must consume the SLO engine's burn-rate signal"
    )
    assert "kdlt_sched_floor_boosts_total" in names, (
        "the HPA must consume the scheduler's starvation-floor signal"
    )
    assert "kdlt_admission_shed_total" in names, (
        "the HPA must consume the admission shed rate (the leading "
        "overload edge -- sheds fire before the burn windows move)"
    )
    assert "kdlt_sched_queue_depth" in names, (
        "the HPA must consume the scheduler queue depth (a standing "
        "queue is the knee before deadline misses)"
    )
    for name in names:
        assert f'"{name}"' in metrics_src, (
            f"HPA scales on {name!r}, which utils/metrics.py does not mint "
            "-- the autoscaler would read a nonexistent series"
        )
    # The burn-rate metric must select a real SLO window label value.
    from kubernetes_deep_learning_tpu.utils import slo as slo_lib

    (burn,) = [
        m["pods"]["metric"] for m in hpa["spec"]["metrics"]
        if m["type"] == "Pods" and m["pods"]["metric"]["name"] == "kdlt_slo_burn_rate"
    ]
    window = burn["selector"]["matchLabels"]["window"]
    assert window in [label for label, _ in slo_lib.WINDOWS]


def test_gateway_hpa_scales_on_minted_shed_signal():
    """The gateway HPA must scale on the admission shed rate -- a signal
    the gateway itself mints -- not CPU (a gateway stalled on slow
    upstreams sheds while its CPU idles); and every metric it names must
    be a literal series name in utils/metrics.py."""
    k8s = os.path.join(DEPLOY, "k8s")
    docs = _yaml_docs(os.path.join(k8s, "gateway-hpa.yaml"))
    (hpa,) = [d for d in docs if d["kind"] == "HorizontalPodAutoscaler"]
    assert hpa["spec"]["scaleTargetRef"]["name"] == "serving-gateway"

    metrics = hpa["spec"]["metrics"]
    assert not any(m["type"] == "Resource" for m in metrics), (
        "CPU-based scaling must be gone: shed rate is the load signal"
    )
    names = [
        m["pods"]["metric"]["name"] for m in metrics if m["type"] == "Pods"
    ]
    assert "kdlt_admission_shed_total" in names
    metrics_src = _read(os.path.join(
        REPO, "kubernetes_deep_learning_tpu", "utils", "metrics.py"
    ))
    for name in names:
        assert f'"{name}"' in metrics_src, (
            f"HPA scales on {name!r}, which utils/metrics.py does not mint"
        )


def test_elastic_fleet_envs_agree_across_k8s_and_compose():
    """Elastic-fleet wiring (ISSUE 11): the gateway's dynamic-membership
    resolve interval and the model tier's AOT-warm boot flag are present
    in BOTH deploy targets with values the code accepts, and the two
    topologies agree -- a compose stack rehearsing a k8s rollout must
    exhibit the same membership churn and warm-boot behavior."""
    from kubernetes_deep_learning_tpu.serving.model_server import AOT_WARM_ENV
    from kubernetes_deep_learning_tpu.serving.upstream import POOL_RESOLVE_ENV

    k8s = os.path.join(DEPLOY, "k8s")
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    services = compose["services"]

    def k8s_env(dep):
        (container,) = dep["spec"]["template"]["spec"]["containers"]
        return {e["name"]: str(e.get("value", "")) for e in container["env"]}

    resolve = {
        "k8s/gateway": k8s_env(gw_dep).get(POOL_RESOLVE_ENV),
        "compose/gateway": str(
            services["gateway"]["environment"].get(POOL_RESOLVE_ENV)
        ),
    }
    assert all(v not in (None, "None") for v in resolve.values()), resolve
    assert len(set(resolve.values())) == 1, (
        f"{POOL_RESOLVE_ENV} disagrees across gateways: {resolve}"
    )
    assert float(next(iter(resolve.values()))) > 0

    warm = {"k8s/model-server": k8s_env(model_dep).get(AOT_WARM_ENV)}
    for svc in ("model-server", "model-server-b"):
        warm[f"compose/{svc}"] = str(
            services[svc]["environment"].get(AOT_WARM_ENV)
        )
    assert all(v not in (None, "None") for v in warm.values()), warm
    assert len(set(warm.values())) == 1, (
        f"{AOT_WARM_ENV} disagrees across the model tiers: {warm}"
    )
    # The value must be one the server's truthy parse accepts.
    assert next(iter(warm.values())).strip().lower() in ("1", "true", "yes")

    # The image-build half of the warm story: the model-server dockerfile
    # bakes the cache with the kdlt-warm console script.
    dockerfile = _read(os.path.join(DEPLOY, "model-server.dockerfile"))
    assert "kdlt-warm" in dockerfile, (
        "model-server.dockerfile must bake the compile cache (kdlt-warm)"
    )


def test_slo_target_agrees_across_every_tier_and_topology():
    """KDLT_SLO_TARGET drives burn rates on BOTH tiers (gateway = client-
    observed, model tier = server-side) and in both topologies; a
    disagreement would make the two views burn at different rates against
    the same traffic, by construction."""
    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    (compose,) = _yaml_docs(os.path.join(DEPLOY, "docker-compose.yaml"))

    def k8s_env(dep, name):
        (container,) = dep["spec"]["template"]["spec"]["containers"]
        return {e["name"]: e.get("value") for e in container["env"]}.get(name)

    targets = {
        "k8s/model-server": k8s_env(model_dep, "KDLT_SLO_TARGET"),
        "k8s/gateway": k8s_env(gw_dep, "KDLT_SLO_TARGET"),
    }
    for svc_name, svc in compose["services"].items():
        targets[f"compose/{svc_name}"] = (
            svc.get("environment", {}).get("KDLT_SLO_TARGET")
        )
    assert all(v is not None for v in targets.values()), targets
    assert len(set(targets.values())) == 1, (
        f"KDLT_SLO_TARGET disagrees across tiers: {targets}"
    )
    # And the value must parse as a usable target.
    from kubernetes_deep_learning_tpu.utils import slo as slo_lib

    value = float(next(iter(targets.values())))
    assert 0.0 < value < 1.0
    assert slo_lib.resolve_target(value) == value


def test_incident_recorder_envs_agree_across_k8s_and_compose():
    """Incident flight-recorder wiring (ISSUE 13): every tier copy in both
    topologies carries the KDLT_INCIDENT_* knobs with values the recorder's
    own parsers accept, the trigger spec / caps agree everywhere (a replica
    pair disagreeing on triggers would capture different incidents for the
    same outage), each tier's bundle dir agrees between compose and k8s,
    and the k8s dirs live on mounted volumes so bundles survive container
    restarts."""
    from kubernetes_deep_learning_tpu.utils.flightrecorder import (
        DIR_ENV,
        MAX_BUNDLES_ENV,
        MAX_MB_ENV,
        TRIGGERS_ENV,
        parse_triggers,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    services = compose["services"]

    def k8s_env(dep):
        (container,) = dep["spec"]["template"]["spec"]["containers"]
        return {e["name"]: str(e.get("value", "")) for e in container["env"]}

    def compose_env(svc):
        return {
            k: str(v) for k, v in services[svc]["environment"].items()
        }

    copies = {
        "k8s/gateway": k8s_env(gw_dep),
        "k8s/model-server": k8s_env(model_dep),
        "compose/gateway": compose_env("gateway"),
        "compose/model-server": compose_env("model-server"),
        "compose/model-server-b": compose_env("model-server-b"),
    }
    # Triggers + caps: present everywhere and identical everywhere.
    for var in (TRIGGERS_ENV, MAX_BUNDLES_ENV, MAX_MB_ENV):
        values = {where: env.get(var) for where, env in copies.items()}
        assert all(v is not None for v in values.values()), (
            f"{var} missing from some tier copy: {values}"
        )
        assert len(set(values.values())) == 1, (
            f"{var} disagrees across tier copies: {values}"
        )
    # The trigger spec must parse through the recorder's own grammar and
    # keep the default rules armed (the deploys must not silently disable
    # a trigger class the runbooks rely on).
    triggers = parse_triggers(copies["k8s/gateway"][TRIGGERS_ENV])
    for name in ("burn-crossing", "brownout", "dispatch-stall",
                 "replica-unhealthy"):
        assert name in triggers, f"deploys dropped the {name} trigger"
    assert int(copies["k8s/gateway"][MAX_BUNDLES_ENV]) > 0
    assert float(copies["k8s/gateway"][MAX_MB_ENV]) > 0

    # Per-tier dir agreement between compose and k8s (the tiers may use
    # different paths -- gateway has no XLA cache volume -- but each
    # tier's compose rehearsal must write where its k8s pod writes).
    for a, b in (("k8s/gateway", "compose/gateway"),
                 ("k8s/model-server", "compose/model-server")):
        assert copies[a].get(DIR_ENV), f"{a} missing {DIR_ENV}"
        assert copies[a][DIR_ENV] == copies[b].get(DIR_ENV), (
            f"{DIR_ENV} disagrees between {a} and {b}"
        )

    # k8s: each tier's bundle dir must live under a mounted volume, or a
    # container restart (the very event an incident precedes) loses the
    # evidence.
    for dep in (gw_dep, model_dep):
        pod = dep["spec"]["template"]["spec"]
        (container,) = pod["containers"]
        env = {e["name"]: str(e.get("value", "")) for e in container["env"]}
        mounts = [m["mountPath"] for m in container.get("volumeMounts", [])]
        assert any(env[DIR_ENV].startswith(m) for m in mounts), (
            f"{DIR_ENV}={env[DIR_ENV]} must live under a mounted volume"
        )


def test_ingest_envs_agree_across_k8s_and_compose():
    """The raw-bytes ingest wiring (ISSUE 20): KDLT_INGEST rides on BOTH
    tiers in BOTH deploy targets with agreeing values -- a gateway with
    the wire on and a model tier without it silently pays the fallback
    decode on every request -- plus the tier-local knobs: the model
    tier's decode pool and the gateway's hoisted fetch fan-out.  Every
    value must parse through the same resolvers the code uses."""
    from kubernetes_deep_learning_tpu.ops.preprocess import (
        DECODE_POOL_ENV,
        resolve_decode_pool,
    )
    from kubernetes_deep_learning_tpu.serving.gateway import (
        FETCH_CONCURRENCY_ENV,
        resolve_fetch_concurrency,
    )
    from kubernetes_deep_learning_tpu.serving.protocol import (
        INGEST_ENV,
        ingest_enabled,
    )

    k8s = os.path.join(DEPLOY, "k8s")
    (model_dep,) = _yaml_docs(os.path.join(k8s, "model-server-deployment.yaml"))
    (gw_dep,) = _yaml_docs(os.path.join(k8s, "gateway-deployment.yaml"))
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))

    def k8s_env(dep):
        (container,) = dep["spec"]["template"]["spec"]["containers"]
        return {e["name"]: str(e.get("value", "")) for e in container["env"]}

    def compose_env(svc):
        return {
            k: str(v)
            for k, v in compose["services"][svc]["environment"].items()
        }

    model_tier = {
        "k8s/model-server": k8s_env(model_dep),
        "compose/model-server": compose_env("model-server"),
        "compose/model-server-b": compose_env("model-server-b"),
    }
    gateway_tier = {
        "k8s/gateway": k8s_env(gw_dep),
        "compose/gateway": compose_env("gateway"),
    }
    for tier, var in (
        (model_tier, INGEST_ENV),
        (model_tier, DECODE_POOL_ENV),
        (gateway_tier, INGEST_ENV),
        (gateway_tier, FETCH_CONCURRENCY_ENV),
    ):
        values = {where: env.get(var) for where, env in tier.items()}
        assert all(v is not None for v in values.values()), (
            f"{var} missing from some copy of the tier: {values}"
        )
        assert len(set(values.values())) == 1, (
            f"{var} disagrees across the tier: {values}"
        )
    # The wire must be ON in both tiers (the negotiation handshake makes
    # a half-on deployment safe, but the shipped posture is on/on), and
    # every value must round-trip the production resolvers.
    ingest_value = model_tier["k8s/model-server"][INGEST_ENV]
    assert ingest_value == gateway_tier["k8s/gateway"][INGEST_ENV], (
        "the two tiers ship disagreeing KDLT_INGEST postures"
    )
    os.environ[INGEST_ENV] = ingest_value
    try:
        assert ingest_enabled() is True, (
            "deploys must not ship the ingest kill switch engaged"
        )
    finally:
        del os.environ[INGEST_ENV]
    pool = resolve_decode_pool(
        int(model_tier["k8s/model-server"][DECODE_POOL_ENV])
    )
    assert 1 <= pool <= 64, "decode pool wired to a nonsense width"
    fetchers = resolve_fetch_concurrency(
        int(gateway_tier["k8s/gateway"][FETCH_CONCURRENCY_ENV])
    )
    assert 1 <= fetchers <= 64, "fetch fan-out wired to a nonsense width"


def test_compose_services_reference_built_dockerfiles():
    compose = yaml.safe_load(_read(os.path.join(DEPLOY, "docker-compose.yaml")))
    for svc in compose["services"].values():
        build = svc.get("build")
        if isinstance(build, dict) and "dockerfile" in build:
            # Compose resolves context relative to the compose FILE, and the
            # dockerfile relative to that context.
            ctx = os.path.normpath(os.path.join(DEPLOY, build.get("context", ".")))
            path = os.path.join(ctx, build["dockerfile"])
            assert os.path.exists(path), f"compose references missing {path}"


def test_lockfile_consistent_with_constraints():
    """requirements.lock (the Pipfile.lock-equivalent transitive closure)
    must agree with constraints.txt's direct pins and cover the runtime
    dependency roots -- images install from the lock (deploy/*.dockerfile)."""
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def pins(path):
        out = {}
        for line in open(os.path.join(root, path)):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            name, _, ver = line.partition("==")
            out[re.sub(r"[-_.]+", "-", name).lower()] = ver
        return out

    constraints = pins("constraints.txt")
    lock = pins("requirements.lock")
    assert len(lock) >= 40, f"suspiciously small lock ({len(lock)} pins)"
    for name, ver in constraints.items():
        assert name in lock, f"{name} pinned in constraints.txt but not locked"
        assert lock[name] == ver, (
            f"{name}: constraints.txt=={ver} but requirements.lock=={lock[name]}"
        )
    for direct in ("jax", "flax", "numpy", "msgpack", "pillow", "requests",
                   "optax", "grpcio", "protobuf", "gunicorn"):
        assert direct in lock, f"runtime root {direct} missing from lock"


def test_dockerfiles_install_from_lock():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for df in ("deploy/gateway.dockerfile", "deploy/model-server.dockerfile"):
        text = open(os.path.join(root, df)).read()
        assert "requirements.lock" in text, f"{df} does not use the lockfile"
        assert "-c requirements.lock" in text or "-c /tmp/requirements.lock" in text
