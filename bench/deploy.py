"""Builds one deployment from its configuration file.

A configuration file (``bench/configs/<name>.json``) states the chip, the
tenants and the controller's options.  ``build`` turns it into the
program's objects through their public constructors.  Its ``tenants``
block names a ``builder``, a module ``bench/configs/<builder>.py`` whose
``tenants(spec)`` returns the networks; a new kind of tenant population
is a new module, found by that name.  Tenants are made from the file
alone, never from the run's seed: every seed meets the same population,
and the traffic mix decides the order in which they come.

Each tenant asks for ``min(tiles_per_tenant, clusters)`` tiles.
"""

from __future__ import annotations

import dataclasses
import importlib

#: HardwareConfig fields a configuration's ``hardware`` block may set
_HW_TIMING = ("t_fire", "t_spike_encode", "t_spike_link", "t_route",
              "e_spike_read", "e_packet_encode", "e_link_hop", "p_tile_idle")


@dataclasses.dataclass
class Deployment:
    config: dict
    hw: object                  # repro.core.HardwareConfig
    tenants: dict               # name -> SNN fields (generators' dicts)
    ctl: object                 # repro.core.AdmissionController
    requests: dict              # name -> tiles asked for


def hardware(block: dict):
    from repro.core import CrossbarConfig, HardwareConfig, TileConfig

    tile = TileConfig(
        crossbar=CrossbarConfig(
            inputs=block["crossbar_inputs"],
            outputs=block["crossbar_outputs"],
            crosspoints=block["crossbar_crosspoints"],
        ),
        input_buffer=block["input_buffer"],
        output_buffer=block["output_buffer"],
        connections=block["connections"],
    )
    return HardwareConfig(
        n_tiles=block["n_tiles"], tile=tile,
        **{k: float(block[k]) for k in _HW_TIMING},
    )


def make_tenants(spec: dict) -> dict:
    builder = importlib.import_module(f"bench.configs.{spec['builder']}")
    return {s["name"]: s for s in builder.tenants(spec)}


def to_snn(fields: dict):
    from repro.core import SNN

    return SNN(**{f.name: fields[f.name] for f in dataclasses.fields(SNN)})


def build(config: dict) -> Deployment:
    """Chip, tenants, controller; every tenant registered (design time)."""
    from repro.core import AdmissionController

    hw = hardware(config["hardware"])
    opts = dict(config["controller"])
    for key in ("joint_budget", "optimize_budget"):
        if opts.get(key) is not None:
            opts[key] = tuple(opts[key])
    ctl = AdmissionController(hw, **opts)
    tenants = make_tenants(config["tenants"])
    cap = config["tenants"]["tiles_per_tenant"]
    requests = {}
    for name, fields in tenants.items():
        art = ctl.register(to_snn(fields))
        requests[name] = max(1, min(cap, art.clustered.n_clusters))
    return Deployment(
        config=config, hw=hw, tenants=tenants, ctl=ctl, requests=requests)
