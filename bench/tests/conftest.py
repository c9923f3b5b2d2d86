"""Runs of the harness replace functions of the program through their
modules' namespaces (spans around calls, planted breaks); every test gets
the program back as it was."""
import pytest


@pytest.fixture(autouse=True)
def restore_program(monkeypatch):
    from repro import api
    from repro.launch import serve_medoid

    for attr in ("pack_queries", "ragged_medoids", "telemetry_to_host",
                 "MedoidServer"):
        monkeypatch.setattr(serve_medoid, attr, getattr(serve_medoid, attr))
    monkeypatch.setattr(api, "find_medoid", api.find_medoid)
