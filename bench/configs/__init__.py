"""Configurations: ``<config>.json`` holds a deployment's sizes, and
``<builder>.py`` the tenant builder that its ``tenants`` block names (see
``bench/deploy.py``)."""
