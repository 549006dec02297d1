"""Tenant builder: the named Table-1 applications at published size."""

from bench import generators


def tenants(spec: dict) -> list:
    return [generators.build_app(name) for name in spec["apps"]]
