"""Launchers: train/serve drivers and the HLO cost walk."""
