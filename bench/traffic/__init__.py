"""Traffic: ``<mix>.json`` holds a mix's parameters, ``<loop>.py`` the
loop that a mix names under ``"loop"`` (see ``bench/loops.py``)."""
