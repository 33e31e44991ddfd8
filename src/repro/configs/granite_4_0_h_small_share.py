"""granite-4.0-h-small as one chip of sixteen holds it: two pipeline stages
of 20 layers, and in each stage eight chips share every expert block, nine
routed experts each.  This chip holds layers 0-19 (two whole periods of
ten, attention at 5 and 15) and experts 0-8 of each; the router still
scores all 72 and every width is the published one."""

import dataclasses

from . import granite_4_0_h_small as published

CONFIG = dataclasses.replace(
    published.CONFIG, name="granite-4.0-h-small-share", n_layers=20,
    layer_types=published.CONFIG.layer_types[:20], experts_held=9)


def smoke_config():
    return dataclasses.replace(published.smoke_config(), name=CONFIG.name)
