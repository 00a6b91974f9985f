# Launch layer: production mesh, dry-run driver, roofline extraction,
# train/serve entry points.  NOTE: importing this package must NOT touch
# jax device state (dryrun.main sets XLA_FLAGS before first jax use).
