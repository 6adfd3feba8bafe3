"""End-to-end CLI runs (synthetic source, cpu + tpu backends)."""

import pytest

from kafka_topic_analyzer_tpu.cli import main, parse_kv_pairs, parse_mesh


def test_parse_kv_pairs():
    assert parse_kv_pairs("a=b,c=d") == {"a": "b", "c": "d"}
    assert parse_kv_pairs(None) == {}


def test_parse_mesh():
    assert parse_mesh("4") == (4, 1)
    assert parse_mesh("4,2") == (4, 2)


def test_help_and_version_surface(capsys):
    """-h/--help/--version must exit 0 and render, like clap's
    (src/main.rs:32-67 — the reference's help cannot crash).

    Regression: a bare ``%`` in an argparse help string makes
    ``format_help()`` raise ValueError at print time (r2-r3 shipped one in
    a flag's help), so every registered action's help is formatted here,
    not just spot-checked flags.
    """
    from kafka_topic_analyzer_tpu.cli import build_parser

    parser = build_parser()
    # Every action's help string must survive argparse's %-interpolation.
    formatter = parser._get_formatter()
    for action in parser._actions:
        if action.help:
            # Same interpolation argparse applies inside format_help().
            formatter._expand_help(action)
    full = parser.format_help()
    assert "--superbatch" in full and "--topic" in full

    for flag in ("-h", "--help"):
        with pytest.raises(SystemExit) as e:
            main([flag])
        assert e.value.code == 0
        out = capsys.readouterr().out
        assert "kafka-topic-analyzer" in out and "--backend" in out

    with pytest.raises(SystemExit) as e:
        main(["--version"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    assert "kafka-topic-analyzer-tpu" in out


@pytest.mark.parametrize("flags", [
    ["--pallas"], ["--wire-format", "v4"], ["--alive-compaction", "off"],
])
def test_removed_path_flags_are_refused(capsys, flags):
    """The packed layout, alive compaction and counter merge have one path
    each: the flags that once chose another are unknown to the parser."""
    with pytest.raises(SystemExit) as e:
        main(["-t", "unit.topic", "--source", "synthetic", *flags])
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


def _run(capsys, extra):
    argv = [
        "-t", "unit.topic",
        "--source", "synthetic",
        "--synthetic", "partitions=2,messages=500,keys=40,tombstones=200",
        "--batch-size", "256",
        "--quiet",
        "--native", "off",
    ] + extra
    assert main(argv) == 0
    return capsys.readouterr().out


def test_cli_cpu_end_to_end(capsys):
    out = _run(capsys, ["--backend", "cpu", "-c"])
    assert "Topic unit.topic" in out
    assert "Alive keys: " in out
    assert "| P | < OS | > OS | Total |" in out
    # 2 partitions * 500 messages
    assert "Topic Size: " in out
    assert out.count("| 0 |") == 1 and out.count("| 1 |") == 1


def test_cli_tpu_matches_cpu_report(capsys):
    out_cpu = _run(capsys, ["--backend", "cpu", "-c", "--alive-bitmap-bits", "24"])
    out_tpu = _run(capsys, ["--backend", "tpu", "-c", "--alive-bitmap-bits", "24"])

    def stable(s: str) -> str:
        # Drop timing-dependent lines.
        return "\n".join(
            l for l in s.splitlines()
            if not l.startswith(("Scanning took:", "Estimated Msg/s:", "Earliest Message:"))
        )

    # Earliest Message depends on scan start time only when the topic has no
    # older message; the synthetic ts range is in the past, so it is stable —
    # but scan start differs between runs by <1s; keep it excluded anyway.
    assert stable(out_cpu) == stable(out_tpu)


def test_tpu_backend_refuses_a_cpu_fallback():
    """`--backend tpu` runs on the host only when JAX_PLATFORMS asks for
    it: a CPU device that JAX fell back to by itself is refused, never
    scanned on in silence."""
    import dataclasses
    import types

    import jax

    from kafka_topic_analyzer_tpu.cli import _make_cli_backend
    from kafka_topic_analyzer_tpu.config import AnalyzerConfig

    cfg = AnalyzerConfig(num_partitions=1, batch_size=64)
    args = types.SimpleNamespace(backend="tpu")
    requested = jax.config.jax_platforms
    jax.config.update("jax_platforms", "")  # as if no platform was asked for
    try:
        with pytest.raises(RuntimeError, match="no accelerator.*JAX_PLATFORMS"):
            _make_cli_backend(args, cfg, (1, 1))
        with pytest.raises(RuntimeError, match="no accelerator"):
            _make_cli_backend(
                args, dataclasses.replace(cfg, mesh_shape=(2, 1)), (2, 1)
            )
        # The cpu oracle has no device to check.
        _make_cli_backend(types.SimpleNamespace(backend="cpu"), cfg, (1, 1))
    finally:
        jax.config.update("jax_platforms", requested)
    assert _make_cli_backend(args, cfg, (1, 1)).device.platform == "cpu"


def test_cli_kafka_source_end_to_end(capsys):
    """The reference-identical invocation: -t topic -b broker."""
    from fake_broker import FakeBroker

    records = {
        0: [(i, 1_600_000_000_000 + i, f"k{i%9}".encode(),
             None if i % 5 == 3 else b"x" * 20) for i in range(100)],
        1: [(i, 1_600_000_000_000 + i, None, b"y" * 30) for i in range(60)],
    }
    with FakeBroker("real.topic", records) as broker:
        assert main([
            "-t", "real.topic",
            "-b", f"127.0.0.1:{broker.port}",
            "--librdkafka", "fetch.wait.max.ms=10,check.crcs=true",
            "-c", "--alive-bitmap-bits", "20",
            "--quiet", "--native", "off",
        ]) == 0
    out = capsys.readouterr().out
    assert "Topic real.topic" in out
    assert "Alive keys: " in out
    # 100 + 60 records scanned
    assert "| 0    | 100  | 100   |" in out


def test_cli_json_output(capsys):
    import json

    assert main([
        "-t", "j.topic", "--source", "synthetic",
        "--synthetic", "partitions=2,messages=300,keys=40,tombstones=200",
        "--backend", "tpu", "-c", "--alive-bitmap-bits", "20",
        "--quantiles", "--json", "--quiet", "--native", "off",
    ]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["topic"] == "j.topic"
    assert doc["overall"]["count"] == 600
    assert set(doc["partitions"]) == {"0", "1"}
    row = doc["partitions"]["0"]
    assert row["total"] == 300
    assert row["total"] == row["alive"] + row["tombstones"]
    assert row["end_offset"] == 300
    assert "alive_keys" in doc and "size_quantiles" in doc


def test_cli_json_multi_topic(capsys):
    import json

    assert main([
        "-t", "x,y", "--source", "synthetic",
        "--synthetic", "partitions=1,messages=200,keys=20",
        "--backend", "cpu", "--json", "--quiet", "--native", "off",
    ]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc["topics"]) == {"x", "y"}
    assert doc["union"]["count"] == 400
    assert doc["topics"]["x"]["overall"]["count"] == 200


def test_cli_empty_topic_exits_minus_2(capsys):
    with pytest.raises(SystemExit) as e:
        main([
            "-t", "empty.topic",
            "--source", "synthetic",
            "--synthetic", "partitions=2,messages=0",
            "--quiet", "--native", "off",
        ])
    assert e.value.code == -2
