"""Pricing seam of the device chain step (``chain.resimulate_chains``)."""
from .chain import resimulate_chains

__all__ = ["resimulate_chains"]
