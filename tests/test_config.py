import os

from advanced_scrapper_tpu.config import DedupConfig, ScraperConfig, from_env, default_config


def test_env_override_coerces_types(monkeypatch):
    monkeypatch.setenv("ASTPU_DEDUP_NUM_PERM", "256")
    monkeypatch.setenv("ASTPU_DEDUP_SIM_THRESHOLD", "0.8")
    cfg = from_env(DedupConfig, "dedup")
    assert cfg.num_perm == 256 and isinstance(cfg.num_perm, int)
    assert cfg.sim_threshold == 0.8 and isinstance(cfg.sim_threshold, float)


def test_env_override_bool(monkeypatch):
    monkeypatch.setenv("ASTPU_ENRICH_HARDENED", "0")
    from advanced_scrapper_tpu.config import EnrichConfig

    assert from_env(EnrichConfig, "enrich").hardened is False
    monkeypatch.setenv("ASTPU_ENRICH_HARDENED", "true")
    assert from_env(EnrichConfig, "enrich").hardened is True


def test_defaults_are_reference_operating_points():
    cfg = default_config()
    # ref constant_rate_scrapper.py:17,20,23,28
    assert cfg.scraper.desired_request_rate == 5.8
    assert cfg.scraper.max_threads == 16
    assert cfg.scraper.stats_time_window == 10.0
    assert cfg.scraper.rate_limit_wait == 200.0
    # ref server1.py:20 / client1.py:23-24
    assert cfg.feed.max_clients == 5
    assert cfg.feed.batch_size == 20
    assert cfg.feed.min_queue_length == 10
    # BASELINE.json north star
    assert (cfg.dedup.shingle_k, cfg.dedup.num_perm, cfg.dedup.num_bands) == (5, 128, 16)


def test_explicit_override_beats_env(monkeypatch):
    monkeypatch.setenv("ASTPU_SCRAPER_MAX_THREADS", "4")
    assert from_env(ScraperConfig, "scraper", max_threads=9).max_threads == 9


def test_env_sections_do_not_collide(monkeypatch):
    """ASTPU_FEED_BATCH_SIZE must not leak into DedupConfig.batch_size."""
    from advanced_scrapper_tpu.config import FeedConfig

    monkeypatch.setenv("ASTPU_FEED_BATCH_SIZE", "20")
    assert from_env(FeedConfig, "feed").batch_size == 20
    assert from_env(DedupConfig, "dedup").batch_size == 1024


def test_env_tuple_coercion(monkeypatch):
    from advanced_scrapper_tpu.config import EnrichConfig

    monkeypatch.setenv("ASTPU_ENRICH_COOLDOWN_EVERY3", "10,20")
    assert from_env(EnrichConfig, "enrich").cooldown_every3 == (10.0, 20.0)


def test_packed_h2d_false_raises():
    """``packed_h2d`` has one legal value; False (or any non-True value)
    raises at construction, naming the migration note."""
    import dataclasses

    import pytest

    with pytest.raises(ValueError, match="MIGRATION.md"):
        DedupConfig(packed_h2d=False)
    with pytest.raises(ValueError, match="packed_h2d"):
        dataclasses.replace(DedupConfig(), packed_h2d=False)
    assert DedupConfig(packed_h2d=True).packed_h2d is True
