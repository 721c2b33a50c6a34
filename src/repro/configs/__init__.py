"""Decomposition configs: :mod:`repro.configs.amped_paper` pins the paper's
own setup (rank, device count, kernel and exchange) on top of the
:mod:`repro.api` presets. Every module here must be a decompose config
(analysis rule AP-C001).
"""
