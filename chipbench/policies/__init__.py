"""Plain numpy balancers (``lb_<name>``) and worker schedulers
(``sched_<name>``) of the reference, one file each."""
