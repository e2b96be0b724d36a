"""Load-time property validation (service/props.py).

Satellite contract: malformed ints/floats/bools for known keys fall back
to their defaults with a warning NAMING the key, and unknown file keys /
``RATELIMITER_*`` env overrides warn instead of passing silently.
"""

import logging

import pytest

from ratelimiter_tpu.service.props import AppProperties


@pytest.fixture(autouse=True)
def _capture_props_log(caplog):
    # setup_logging (run by any earlier service test) turns off propagation
    # on the package root; caplog's handler sits on the root logger.
    from ratelimiter_tpu.utils.logging import ROOT

    logger = logging.getLogger(ROOT)
    was = logger.propagate
    logger.propagate = True
    caplog.set_level(logging.WARNING, logger=f"{ROOT}.service.props")
    yield caplog
    logger.propagate = was


def test_malformed_int_falls_back_to_default(caplog):
    props = AppProperties({"batcher.max_batch": "81q2"})
    assert props.get_int("batcher.max_batch", -1) == 8192  # the default
    assert any("batcher.max_batch" in rec.message for rec in caplog.records)


def test_malformed_float_falls_back_to_default(caplog):
    props = AppProperties({"breaker.open_ms": "five seconds"})
    assert props.get_float("breaker.open_ms", -1.0) == 5000.0
    assert any("breaker.open_ms" in rec.message for rec in caplog.records)


def test_malformed_bool_falls_back_to_default(caplog):
    props = AppProperties({"breaker.enabled": "maybe"})
    assert props.get_bool("breaker.enabled") is True
    assert any("breaker.enabled" in rec.message for rec in caplog.records)


def test_wellformed_values_pass_silently(caplog):
    props = AppProperties({
        "batcher.max_batch": "1024",
        "breaker.open_ms": "250.5",
        "breaker.enabled": "off",
        "ratelimiter.overload.max_pending": "128",
    })
    assert props.get_int("batcher.max_batch") == 1024
    assert props.get_float("breaker.open_ms") == 250.5
    assert props.get_bool("breaker.enabled") is False
    assert props.get_int("ratelimiter.overload.max_pending") == 128
    assert not caplog.records


def test_unknown_file_key_warns_but_is_kept(caplog):
    props = AppProperties({"ratelimiter.overlod.max_pending": "10"})  # typo
    assert any("ratelimiter.overlod.max_pending" in rec.message
               for rec in caplog.records)
    assert props.get("ratelimiter.overlod.max_pending") == "10"


def test_env_override_applies_and_unknown_env_warns(
        caplog, monkeypatch, tmp_path):
    monkeypatch.setenv("RATELIMITER_BREAKER_FAILURE_THRESHOLD", "3")
    monkeypatch.setenv("RATELIMITER_BRAKER_OPEN_MS", "100")  # typo
    props = AppProperties.load(str(tmp_path / "missing.properties"))
    assert props.get_int("breaker.failure_threshold") == 3
    assert any("RATELIMITER_BRAKER_OPEN_MS" in rec.message
               for rec in caplog.records)


def test_env_direct_keys_do_not_warn(caplog, monkeypatch, tmp_path):
    # Env vars read directly by engine/ops modules are exempt from the
    # unknown-key scan.
    monkeypatch.setenv("RATELIMITER_PALLAS", "1")
    AppProperties.load(str(tmp_path / "missing.properties"))
    assert not any("RATELIMITER_PALLAS" in rec.message
                   for rec in caplog.records)


def test_malformed_env_override_falls_back(caplog, monkeypatch, tmp_path):
    monkeypatch.setenv("RATELIMITER_SERVER_PORT", "eight-thousand")
    props = AppProperties.load(str(tmp_path / "missing.properties"))
    assert props.get_int("server.port") == 8080
    assert any("server.port" in rec.message for rec in caplog.records)
