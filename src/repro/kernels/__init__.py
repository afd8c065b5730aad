"""Pallas kernels of the match and query planes.

Every ``pallas_call`` here runs in the Pallas interpreter on the CPU
backend (tests, CPU rehearsals) and is compiled by Mosaic everywhere else
(``repro.device.on_cpu``); there is no option to choose.
"""
