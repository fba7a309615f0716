"""Contracts assembled with vm/builder.py that the repo itself deploys (the
benchmark's Smallbank cell, DEPLOY.md's worked example)."""
