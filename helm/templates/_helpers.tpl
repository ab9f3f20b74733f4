{{/* Common labels */}}
{{- define "rag.labels" -}}
app.kubernetes.io/part-of: {{ .Chart.Name }}
app.kubernetes.io/managed-by: {{ .Release.Service }}
app.kubernetes.io/instance: {{ .Release.Name }}
{{- end -}}

{{/* Image reference */}}
{{- define "rag.image" -}}
{{ .Values.image.repository }}:{{ .Values.image.tag }}
{{- end -}}

{{/* Hostnames of the infra services (bitnami subchart naming) */}}
{{- define "rag.cassandraHost" -}}
{{ .Release.Name }}-cassandra
{{- end -}}
{{- define "rag.redisHost" -}}
{{ .Release.Name }}-redis-master
{{- end -}}
{{- define "rag.modelServerHost" -}}
model-server
{{- end -}}
{{- define "rag.pushgatewayHost" -}}
{{ .Release.Name }}-prometheus-pushgateway
{{- end -}}

{{/* nc-loop initContainer waiting for a TCP service; args: dict host port name */}}
{{- define "rag.waitFor" -}}
- name: wait-for-{{ .name }}
  image: busybox:1.36
  command: ['sh', '-c', 'until nc -z {{ .host }} {{ .port }}; do echo waiting for {{ .name }}; sleep 3; done']
{{- end -}}

{{/* Env block shared by api / worker / ingest pods.  These pods request no
     TPU and say so with JAX_PLATFORMS=cpu: runtime.on_tpu() reads an unpinned
     CPU backend as a chip that went missing and refuses to start. */}}
{{- define "rag.commonEnv" -}}
- name: JAX_PLATFORMS
  value: "cpu"
- name: REDIS_URL
  value: "redis://{{ include "rag.redisHost" . }}:6379/0"
- name: CASSANDRA_HOST
  value: {{ include "rag.cassandraHost" . | quote }}
- name: CASSANDRA_PORT
  value: "9042"
- name: CASSANDRA_USERNAME
  value: {{ .Values.cassandra.dbUser.user | quote }}
- name: CASSANDRA_PASSWORD
  value: {{ .Values.cassandra.dbUser.password | quote }}
- name: CASSANDRA_KEYSPACE
  value: {{ .Values.cassandra.keyspace | quote }}
- name: STORE_BACKEND
  value: "cassandra"
- name: QWEN_ENDPOINT
  value: "http://{{ include "rag.modelServerHost" . }}:{{ .Values.modelServer.port }}"
- name: QWEN_MODEL
  value: {{ .Values.modelServer.model.name | quote }}
- name: CONTEXT_WINDOW
  value: {{ .Values.modelServer.model.contextWindow | quote }}
- name: EMBED_MODEL
  value: {{ .Values.embeddings.weightsPath | default .Values.embeddings.model | quote }}
- name: EMBED_DIM
  value: {{ .Values.embeddings.dim | quote }}
- name: MAX_RAG_ATTEMPTS
  value: {{ .Values.agent.maxRagAttempts | quote }}
- name: MIN_SOURCE_NODES
  value: {{ .Values.agent.minSourceNodes | quote }}
- name: ROUTER_TOP_K
  value: {{ .Values.agent.routerTopK | quote }}
- name: DATA_DIR
  value: "/data"
{{- end -}}
