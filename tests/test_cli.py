"""CLI surface: score (end-to-end slice), watch/unwatch, rules.

The score test is the SURVEY.md section 7.3 "minimum end-to-end slice": a
reference-wire-format request judged against the golden demo traces, with
the response in the reference's DocumentResponse shape.
"""

import io
import json
import os

import pytest

from foremast_tpu.cli import main

DATA = os.path.join(os.path.dirname(__file__), "data")
NORMAL = os.path.join(DATA, "demo_canary_normal.csv")
SPIKE = os.path.join(DATA, "demo_canary_spike.csv")


def make_request(tmp_path, aliases=("error4xx",)):
    def mq(query):
        return {
            "dataSourceType": "prometheus",
            "parameters": {
                "endpoint": "http://prometheus:9090/api/v1/",
                "query": query,
                "start": "1600000000",
                "end": "1600000600",
                "step": "60",
            },
        }

    req = {
        "appName": "demo-app",
        "startTime": "2020-09-13T12:26:40Z",
        "endTime": "2020-09-13T12:36:40Z",
        "strategy": "canary",
        "metrics": {
            "current": {a: mq(f"cur:{a}") for a in aliases},
            "baseline": {a: mq(f"base:{a}") for a in aliases},
            "historical": {a: mq(f"hist:{a}") for a in aliases},
        },
    }
    path = tmp_path / "request.json"
    path.write_text(json.dumps(req))
    return str(path)


def run_score(capsys, request_path, current, baseline, historical):
    argv = ["score", "--request", request_path]
    for alias, path in current.items():
        argv += ["--current", f"{alias}={path}"]
    for alias, path in baseline.items():
        argv += ["--baseline", f"{alias}={path}"]
    for alias, path in historical.items():
        argv += ["--historical", f"{alias}={path}"]
    rc = main(argv)
    out = capsys.readouterr().out
    return rc, json.loads(out)


def test_score_spike_trace_is_anomaly(tmp_path, capsys):
    req = make_request(tmp_path)
    rc, resp = run_score(
        capsys,
        req,
        current={"error4xx": SPIKE},
        baseline={"error4xx": NORMAL},
        historical={"error4xx": NORMAL},
    )
    assert rc == 0
    # external status enum (converter.go:11-30): unhealthy -> "anomaly"
    assert resp["status"] == "anomaly"
    assert resp["anomalyInfo"]["values"]["error4xx"], "flat [t,v,...] pairs"
    # flat pair encoding: even length, alternating time/value
    pairs = resp["anomalyInfo"]["values"]["error4xx"]
    assert len(pairs) % 2 == 0
    values = pairs[1::2]
    assert any(v > 30 for v in values), "the 40.134 spike should be flagged"


def test_score_normal_trace_is_healthy(tmp_path, capsys):
    req = make_request(tmp_path)
    rc, resp = run_score(
        capsys,
        req,
        current={"error4xx": NORMAL},
        baseline={"error4xx": NORMAL},
        historical={"error4xx": NORMAL},
    )
    assert rc == 0
    assert resp["status"] == "success"


def test_score_unknown_alias_rejected(tmp_path, capsys):
    req = make_request(tmp_path)
    with pytest.raises(SystemExit):
        main(["score", "--request", req, "--current", f"nope={NORMAL}"])


def test_score_reads_stdin(tmp_path, capsys, monkeypatch):
    req_path = make_request(tmp_path)
    monkeypatch.setattr(
        "sys.stdin", io.StringIO(open(req_path).read())
    )
    rc, resp = run_score(
        capsys,
        "-",
        current={"error4xx": NORMAL},
        baseline={"error4xx": NORMAL},
        historical={"error4xx": NORMAL},
    )
    assert rc == 0 and resp["status"] == "success"


def test_rules_prints_manifest(capsys):
    import yaml

    rc = main(["rules", "--namespace", "observ"])
    assert rc == 0
    parsed = yaml.safe_load(capsys.readouterr().out)
    assert parsed["kind"] == "PrometheusRule"
    assert parsed["metadata"]["namespace"] == "observ"


def test_watch_unwatch_toggle_continuous(monkeypatch, capsys):
    from foremast_tpu.watch.crds import DeploymentMonitor
    from foremast_tpu.watch.kubeapi import InMemoryKube

    from foremast_tpu.watch.crds import MonitorStatus

    kube = InMemoryKube()
    kube.upsert_monitor(
        DeploymentMonitor(
            name="demo", namespace="ns1", status=MonitorStatus(job_id="job-42")
        )
    )
    monkeypatch.setattr(
        "foremast_tpu.watch.kubeapi.HttpKube", lambda base_url=None: kube
    )
    rc = main(["watch", "demo", "-n", "ns1"])
    assert rc == 0
    assert kube.get_monitor("ns1", "demo").continuous is True
    rc = main(["unwatch", "demo", "-n", "ns1"])
    assert rc == 0
    assert kube.get_monitor("ns1", "demo").continuous is False
    # merge-patch semantics: untouched fields survive the toggle
    assert kube.get_monitor("ns1", "demo").status.job_id == "job-42"
    out = capsys.readouterr().out
    assert "watching application demo" in out
    assert "Job: job-42" in out


def test_watch_missing_monitor_fails(monkeypatch, capsys):
    from foremast_tpu.watch.kubeapi import InMemoryKube

    monkeypatch.setattr(
        "foremast_tpu.watch.kubeapi.HttpKube", lambda base_url=None: InMemoryKube()
    )
    assert main(["watch", "ghost", "-n", "ns1"]) == 1


def test_score_honors_env_config(tmp_path, capsys, monkeypatch):
    """cmd_score must build its worker from BrainConfig.from_env() — the
    reference brain is configured entirely through env vars
    (foremast-brain/README.md:20-38). A near-zero threshold must flip
    even the normal trace to anomaly; the indexed rule matrix would be
    silently ignored if score used BrainConfig() defaults."""
    monkeypatch.setenv("metric_type_threshold_count", "1")
    monkeypatch.setenv("metric_type0", "error4xx")
    monkeypatch.setenv("threshold0", "0.0001")
    req = make_request(tmp_path)
    rc, resp = run_score(
        capsys,
        req,
        current={"error4xx": NORMAL},
        baseline={"error4xx": NORMAL},
        historical={"error4xx": NORMAL},
    )
    assert resp["status"] == "anomaly"


def test_compile_cache_resolver(tmp_path, monkeypatch):
    """device.enable_compile_cache: with JAX_COMPILATION_CACHE_DIR set
    JAX has already read it, so the resolver sets NO directory; unset,
    the cache sits at the fixed <checkout>/.jax_cache (the path is part
    of the cache key — never a temp dir). Both threshold overrides are
    applied either way."""
    import os

    import jax

    from foremast_tpu import device

    flags = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {f: getattr(jax.config, f) for f in flags}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        # set from outside: whatever JAX holds stays untouched
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(repo, ".jax_cache")
        assert device.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        for f, v in prev.items():
            jax.config.update(f, v)
